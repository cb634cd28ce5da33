"""The blocked EM step of ``GaussianMixture`` (``ops/base.py::em_step``)
held to the plain reference (``benchmark/reference/em.py``: the textbook
iteration, two passes over the rows, nothing of the program), for all four
covariance types, on one device and on the suite's virtual mesh, with
ragged last blocks and padding rows; and what the issue that brought it
promised of it: no array of rows x k x d or rows x k elements, the shifted
one-pass covariance where raw moments lose it, ``score`` and ``predict``
through the same blocks, the counter and the spans, and a start that runs
no KMeans when all three parameters are given.

Tolerances.  Program and reference are both float32 at 'highest' and sum
in another order (one pass about the old means against two passes about
the new ones; blocks of another size): a sum over n rows of float32 terms
differs by a few 1e-7 of its size between two orders, a mean or a
covariance entry by that over its own size, and ten iterations compound
it by the EM map's own contraction (under 1).  So 2e-5 of the distance
the means moved, 5e-5 of a covariance's norm (5e-4 spherical and tied at
8 devices: fewer numbers average the same rounding), 2e-6 of a lower
bound.  Readings on this rig are 10 to 100 times smaller.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dislib_tpu as ds
from dislib_tpu.cluster import GaussianMixture
from dislib_tpu.cluster import gm as _gm
from dislib_tpu.ops import base as _ops
from dislib_tpu.utils import profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import em as ref  # noqa: E402

COV_TYPES = ("full", "tied", "diag", "spherical")
K, D = 3, 6
# 8 x 1126 rows on the mesh, 9001 on one device: with blocks of 512 rows
# (below) every device ends on a ragged block, and the last rows are padding
ROWS = 9001


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
    monkeypatch.setattr(_ops, "_EM_TILE_BYTES", 4 * K * D * 512)


def _data(seed, rows=ROWS, offset=0.0):
    rng = np.random.RandomState(seed)
    mu = rng.rand(K, D) * 5 + offset
    a = np.eye(D) + 0.3 * rng.randn(K, D, D) / np.sqrt(D)
    comp = rng.randint(0, K, rows)
    x = mu[comp] + np.einsum("bd,bde->be", rng.randn(rows, D), a[comp])
    return x.astype(np.float32), mu.astype(np.float32)


def _start(mu, cov_type, seed=1, spread=0.5):
    """``(weights, means, precisions)`` as the ``*_init`` arguments take
    them, and the covariances the reference starts from."""
    rng = np.random.RandomState(seed)
    means = (mu + spread * rng.randn(K, D)).astype(np.float32)
    prec = {"full": np.tile(np.eye(D, dtype=np.float32), (K, 1, 1)),
            "tied": np.eye(D, dtype=np.float32),
            "diag": np.ones((K, D), np.float32),
            "spherical": np.ones((K,), np.float32)}[cov_type] / 1.5
    covs = (np.linalg.inv(prec) if prec.ndim >= 2 and cov_type != "diag"
            else 1.0 / prec).astype(np.float32)
    return (np.full((K,), 1.0 / K, np.float32), means, prec), covs


def _fit(x, start, cov_type, n_iter):
    weights, means, prec = start
    return GaussianMixture(
        n_components=K, covariance_type=cov_type, max_iter=n_iter, tol=0.0,
        weights_init=weights, means_init=means, precisions_init=prec
    ).fit(ds.array(x))


def _reference_rows(x):
    """The reference visits whole blocks: the rows in blocks that divide
    them (9001 = 9001 x 1, so one block)."""
    return jnp.asarray(x), x.shape[0]


def _compared(gm):
    """A fit as ``ref.compare`` takes it."""
    return {"weights": gm.weights_, "means": gm.means_,
            "covariances": gm.covariances_, "history": gm.history_,
            "lower_bound": gm.lower_bound_, "n_iter": gm.n_iter_}


# -- (a) the fit against the reference ----------------------------------------

@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("cov_type", COV_TYPES)
def test_fit_agrees_with_the_plain_reference(small_blocks, cov_type,
                                             devices):
    _mesh_of(devices)
    x, mu = _data(3)
    start, covs0 = _start(mu, cov_type)
    n_iter = 10
    gm = _fit(x, start, cov_type, n_iter)
    xr, block = _reference_rows(x)
    w, m, c, hist = ref.fit(xr, (start[0], start[1], covs0), n_iter, block,
                            cov_type=cov_type, reg_covar=1e-6)
    gaps = ref.compare(_compared(gm), w, m, c, hist,
                       (start[0], start[1], covs0), n_iter)
    loose = 5e-4 if cov_type in ("tied", "spherical") else 5e-5
    assert gaps["n_iter_gap"] == 0
    assert gaps["first_bound_gap"] < 2e-6, gaps
    assert gaps["bound_gap"] < 2e-6, gaps
    assert gaps["means_gap"] < 2e-5, gaps
    assert gaps["weights_gap"] < 2e-5, gaps
    assert gaps["covariances_gap"] < loose, gaps
    assert gm.covariances_.shape == c.shape
    assert len(gm.history_) == n_iter


def test_blocks_are_derived_from_the_shapes():
    # the cell's shapes: 7 680 rows, which divide 24M, under a 32 MiB tile
    # of (block, 16, 56) float32
    assert _ops.em_block(24_000_000, 50, 16) == 7_680
    assert 24_000_000 % 7_680 == 0
    # all rows where they fit; a ragged budget block where none divides
    assert _ops.em_block(9_001, 50, 16) == 9_001
    assert _ops.em_block(40_000, 50, 16) == 9_216
    assert _ops.em_block(1_000_003, 50, 16) == 9_216
    # never under one quantum, however wide a row's tile
    assert _ops.em_block(10_000, 512, 256) == 512


def test_the_packed_short_contraction_is_the_highest_one(monkeypatch):
    """``pdot_short`` on a backend that packs: the six bfloat16 passes of
    'highest' side by side along the contraction, so within a few 2^-24 of
    the float64 product, entry by entry against the entry's own scale;
    where it does not pack it is ``pdot``."""
    from dislib_tpu.ops import precision as px
    rng = np.random.RandomState(0)
    a = rng.randn(300, 50).astype(np.float32)
    b = rng.randn(50, 96).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    plain = np.asarray(px.pdot_short(jnp.asarray(a), jnp.asarray(b)))
    assert np.array_equal(plain, np.asarray(px.pdot(jnp.asarray(a),
                                                    jnp.asarray(b))))
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    packed = np.asarray(px.pdot_short(jnp.asarray(a), jnp.asarray(b)))
    assert packed.dtype == np.float32 and packed.shape == (300, 96)
    assert np.max(np.abs(packed - want) / scale) < 4 * 2.0 ** -24
    # one pass of bfloat16 would read 2^-9 of the scale: a thousand times
    one = (a.astype(jnp.bfloat16).astype(np.float64)
           @ b.astype(jnp.bfloat16).astype(np.float64))
    assert np.max(np.abs(one - want) / scale) > 1e-4
    # the bfloat16 policy is left as it is
    assert np.array_equal(
        np.asarray(px.pdot_short(jnp.asarray(a), jnp.asarray(b),
                                 px.BFLOAT16)),
        np.asarray(px.pdot(jnp.asarray(a), jnp.asarray(b), px.BFLOAT16)))


def test_the_packed_tall_product_is_the_highest_one(monkeypatch):
    """``pdot_tall`` on a backend that packs: the parts of the narrow
    operand side by side, three bfloat16 GEMMs and their slices added, so
    within a few 2^-24 of the float64 product, entry by entry against the
    entry's own scale; where it does not pack it is ``peinsum``."""
    from dislib_tpu.ops import precision as px
    rng = np.random.RandomState(1)
    a = rng.randn(300, 96).astype(np.float32)
    b = rng.randn(300, 51).astype(np.float32)
    wide = rng.randn(300, 128).astype(np.float32)
    want = a.astype(np.float64).T @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64).T @ np.abs(b).astype(np.float64)

    def gram(u, v, *policy):
        return np.asarray(px.peinsum("bp,bq->pq", jnp.asarray(u),
                                     jnp.asarray(v), *policy))

    def tall(u, v, *policy):
        return np.asarray(px.pdot_tall(jnp.asarray(u), jnp.asarray(v),
                                       *policy))

    assert not px.packs_tall(51, np.dtype(np.float32))
    assert np.array_equal(tall(a, b), gram(a, b))
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    packed = tall(a, b)
    assert packed.dtype == np.float32 and packed.shape == (96, 51)
    assert not np.array_equal(packed, gram(a, b))
    assert np.max(np.abs(packed - want) / scale) < 4 * 2.0 ** -24
    # one pass of bfloat16 would read 2^-9 of the scale: a thousand times
    one = (a.astype(jnp.bfloat16).astype(np.float64).T
           @ b.astype(jnp.bfloat16).astype(np.float64))
    assert np.max(np.abs(one - want) / scale) > 1e-4
    # decided from the shapes: a narrow side that fills whole column
    # tiles has nothing to pack, nor have operands that are not float32
    f32 = np.dtype(np.float32)
    assert [px.packs_tall(n, f32) for n in (50, 51, 64, 100, 128, 256)] \
        == [True, True, True, False, False, False]
    assert not px.packs_tall(51, np.dtype(np.float64))
    assert np.array_equal(tall(a, wide), gram(a, wide))
    # the bfloat16 policy is left as it is
    assert not px.packs_tall(51, f32, px.BFLOAT16)
    assert np.array_equal(tall(a, b, px.BFLOAT16), gram(a, b, px.BFLOAT16))


@pytest.fixture
def packing(monkeypatch):
    """What a TPU runs: the full-covariance E-step's GEMM packed along its
    short contraction and the M-step's on its narrow side."""
    from dislib_tpu.ops import precision as px
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    jax.clear_caches()
    yield
    jax.clear_caches()                  # no packed program is left behind


@pytest.fixture
def small_mxu(monkeypatch):
    """An MXU six columns wide, so that the M-step's wide operand at d = 6,
    k = 3 (18 columns (p, j)) fills more than one of its tiles and its
    product is cut in more than one group of p (the E-step's groups stay
    one: a factor's 6 columns are one chunk)."""
    from dislib_tpu.ops import precision as px
    monkeypatch.setattr(px, "_MXU_COLUMNS", 6)


@pytest.mark.parametrize("devices", [1, 8])
def test_the_fit_with_the_packed_products_agrees_with_the_reference(
        small_blocks, packing, small_mxu, devices):
    _mesh_of(devices)
    assert len(_ops.em_moment_groups(D, K, np.dtype(np.float32))) > 1
    x, mu = _data(3)
    start, covs0 = _start(mu, "full")
    profiling.reset_counters()
    gm = _fit(x, start, "full", 10)
    assert profiling.schedule_counters()["gm_m_step:packed"] == 1
    assert profiling.schedule_counters()["gm_m_moments:upper"] == 1
    assert profiling.schedule_counters()["gm_e_step:triangle"] == 1
    assert np.array_equal(gm.covariances_,
                          np.swapaxes(gm.covariances_, 1, 2))
    xr, block = _reference_rows(x)
    ref_start = (start[0], start[1], covs0)
    w, m, c, hist = ref.fit(xr, ref_start, 10, block)
    gaps = ref.compare(_compared(gm), w, m, c, hist, ref_start, 10)
    assert gaps["bound_gap"] < 2e-6 and gaps["means_gap"] < 2e-5 \
        and gaps["covariances_gap"] < 5e-5, gaps


def _start_sums(x, about, labels):
    """``em_start``'s ``(nk, s, S)`` for full covariances about ``about``,
    from hard labels, on the mesh as it stands."""
    xa = ds.array(x)
    lab = ds.array(labels[:, None].astype(np.int32))
    return [np.asarray(part) for part in jax.jit(
        lambda xp, lp: _ops.em_start(xp, x.shape[0], jnp.asarray(about),
                                     "full", labels=lp))(xa._data, lab._data)]


@pytest.mark.parametrize("devices", [1, 8])
def test_the_start_through_the_packed_product_gives_its_present_answers(
        small_blocks, small_mxu, monkeypatch, devices):
    """``em_start`` runs the M-step's sums without an E-step: the packed
    product, cut in groups of p along the moments' symmetry, and the wide
    operand in its other order give what the six-pass one gives, and that
    is the float64 sums of the labelled rows."""
    from dislib_tpu.ops import precision as px
    _mesh_of(devices)
    x, mu = _data(17)
    labels = np.random.RandomState(2).randint(0, K, ROWS)
    about = mu + 0.25
    plain = _start_sums(x, about, labels)
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    assert len(_ops.em_moment_groups(D, K, np.dtype(np.float32))) > 1
    jax.clear_caches()
    try:
        packed = _start_sums(x, about, labels)
    finally:
        jax.clear_caches()
    diff = x.astype(np.float64)[:, None, :] - about.astype(np.float64)[None]
    hot = np.eye(K)[labels]
    want = (hot.sum(0), np.einsum("bj,bjp->jp", hot, diff),
            np.einsum("bj,bjp,bjq->jpq", hot, diff, diff))
    for got, was, truth in zip(packed, plain, want):
        assert got.shape == was.shape == truth.shape
        assert np.linalg.norm(got - was) <= 2e-6 * np.linalg.norm(truth)
        assert np.linalg.norm(got - truth) <= 2e-6 * np.linalg.norm(truth)


# -- (a'') the M-step cut along the second moments' symmetry ------------------

def _block_moments(x, about, resp):
    """``(nk, s, S)`` of one block's M-step sums about ``about``, as the
    backend at hand takes them."""
    f32 = [jnp.asarray(a, jnp.float32) for a in (x, about, resp)]
    return [np.asarray(v) for v in jax.jit(
        lambda xc, a, r: _ops._em_sums_about(
            _ops._em_block_sums(xc, None, r, a, "full"), a, "full"))(
        f32[0], f32[1], f32[2])]


@pytest.mark.parametrize("k", [1, 3, 16])
@pytest.mark.parametrize("d", [8, 16, 17, 50, 64, 100])
def test_the_cut_m_step_is_the_whole_one(monkeypatch, d, k):
    """The full-covariance M-step's sums cut along the symmetry of S_j (a
    product a group of p, each against the columns q >= p0, the upper
    triangle mirrored: what a backend that packs runs) against the ONE
    product of every other backend and against float64: the same six
    products of the same parts, so both within float32 rounding of the
    truth; S exactly symmetric where the cut engages; and where the
    product does not pack (d + 1 of 101 columns) one product, as before."""
    from dislib_tpu.ops import precision as px
    rng = np.random.RandomState(10 * d + k)
    x = rng.randn(700, d).astype(np.float32)
    about = (0.5 * rng.randn(k, d)).astype(np.float32)
    resp = rng.rand(700, k).astype(np.float32)
    diff = x.astype(np.float64)[:, None, :] - about.astype(np.float64)[None]
    r64 = resp.astype(np.float64)
    want = (r64.sum(0), np.einsum("bj,bjp->jp", r64, diff),
            np.einsum("bj,bjp,bjq->jpq", r64, diff, diff))
    whole = _block_moments(x, about, resp)
    f32 = np.dtype(np.float32)
    assert _ops.em_moment_groups(d, k, f32) == ((0, d),)
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    groups = _ops.em_moment_groups(d, k, f32)
    cut = _block_moments(x, about, resp)
    for got, was, truth in zip(cut, whole, want):
        assert got.shape == was.shape == truth.shape
        assert np.linalg.norm(got - truth) <= 2e-6 * np.linalg.norm(truth)
        assert np.linalg.norm(got - was) <= 2e-6 * np.linalg.norm(truth)
    if _ops.em_packs(d, f32):
        assert np.array_equal(cut[2], np.swapaxes(cut[2], 1, 2))
        assert len(groups) == -(-d // max(
            _ops._MOMENT_TILES * px._MXU_COLUMNS // k, 1))
    else:
        assert d == 100 and groups == ((0, d),)
        assert all(np.array_equal(g, w) for g, w in zip(cut, whole))


def test_the_m_step_groups_follow_the_mxu_tiles(monkeypatch):
    """The rule, from the shapes alone: groups of p that tile [0, d), each
    as many p as fill ``_MOMENT_TILES`` whole tiles of the wide operand's
    (p, j) columns and starting where the one before ended; a group's
    product is (its p times k, d - p0 + 1): the narrow operand's columns
    from its own p0 on, and the ones.  The cell's cut is pinned."""
    from dislib_tpu.ops import precision as px
    f32 = np.dtype(np.float32)
    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    tile = _ops._MOMENT_TILES * px._MXU_COLUMNS
    for d, k in [(8, 1), (17, 3), (50, 16), (64, 16), (50, 48), (50, 200),
                 (84, 5)]:
        groups = _ops.em_moment_groups(d, k, f32)
        assert groups[0][0] == 0 and groups[-1][1] == d
        assert all(a[1] == b[0] for a, b in zip(groups, groups[1:]))
        span = groups[0][1] - groups[0][0]
        assert span == min(max(tile // k, 1), d)
        assert all(p0 % span == 0 and p1 - p0 <= span for p0, p1 in groups)
        shapes = [z.shape for z in _ops._em_zero_sums(k, d, "full", f32)[2]]
        assert shapes == [((p1 - p0) * k, d - p0 + 1) for p0, p1 in groups]
        made = jax.eval_shape(
            lambda xc, r, a: _ops._em_block_sums(xc, None, r, a, "full"),
            jax.ShapeDtypeStruct((512, d), jnp.float32),
            jax.ShapeDtypeStruct((512, k), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32))[2]
        assert [m.shape for m in made] == shapes
    assert _ops.em_moment_groups(50, 16, f32) == (
        (0, 16), (16, 32), (32, 48), (48, 50))
    # where the M-step's product does not pack, one group of all d
    assert _ops.em_moment_groups(85, 16, f32) == ((0, 85),)
    monkeypatch.setattr(px, "_PACK_BACKENDS", ())
    assert _ops.em_moment_groups(50, 16, f32) == ((0, 50),)


# -- (a') the E-step cut along the factors' triangle --------------------------

def _mixture(seed, d, k):
    """Parameters of a mixture as the E-step takes them, in float64:
    log-weights, means, and the upper Cholesky factors of the precisions
    (``_chol_precisions``' convention), and 300 rows around the means."""
    rng = np.random.RandomState(seed)
    weights = rng.rand(k) + 0.5
    means = 3.0 * rng.rand(k, d)
    a = np.eye(d) + 0.4 * rng.randn(k, d, d) / np.sqrt(d)
    covs = np.einsum("jpd,jqd->jpq", a, a)
    prec = np.stack([np.linalg.inv(np.linalg.cholesky(c)).T for c in covs])
    x = means[rng.randint(0, k, 300)] + rng.randn(300, d)
    return np.log(weights / weights.sum()), means, prec, x


def _log_prob(x, log_weights, means, prec):
    """``_em_log_prob`` of the rows ``x`` at these parameters, as the
    backend at hand runs it."""
    f32 = [jnp.asarray(a, jnp.float32) for a in (x, log_weights, means, prec)]
    return np.asarray(jax.jit(lambda xb, lw, mu, p: _ops._em_log_prob(
        xb, _ops.em_whitener(lw, mu, p, "full")))(*f32))


@pytest.mark.parametrize("k", [1, 3, 16])
@pytest.mark.parametrize("d", [8, 16, 17, 50, 64, 100])
def test_the_cut_e_step_is_the_whole_one(monkeypatch, d, k):
    """The full-covariance E-step cut along its factors' triangle (what a
    backend that packs runs) against the one GEMM of every other backend
    and against float64: the same six products less those with the zeros
    under a factor's diagonal, so both within float32 rounding of the
    truth; the groups as the shapes give them; and the strictly lower
    triangle of the factors is never read."""
    from dislib_tpu.ops import precision as px
    log_w, means, prec, x = _mixture(100 * d + k, d, k)
    y = np.einsum("bjd,jde->bje", x[:, None, :] - means[None], prec)
    want = log_w + np.log(np.diagonal(prec, axis1=1, axis2=2)).sum(1) \
        - 0.5 * d * np.log(2 * np.pi) - 0.5 * (y * y).sum(2)
    # what an entry's rounding is measured against: d squared differences
    room = 1e-6 * (np.abs(y) * np.einsum(
        "bjd,jde->bje", np.abs(x[:, None, :] - means[None]),
        np.abs(prec))).sum(2) + 1e-6 * np.abs(want)
    f32 = np.dtype(np.float32)
    assert _ops.em_groups(d, f32) == ((0, d, d),)
    whole = _log_prob(x, log_w, means, prec)
    assert whole.shape == (300, k) and np.all(np.abs(whole - want) <= room)
    poisoned = np.where(np.tri(d, k=-1, dtype=bool), np.nan, prec)
    assert np.array_equal(_log_prob(x, log_w, means, poisoned), whole)

    monkeypatch.setattr(px, "_PACK_BACKENDS", (jax.default_backend(),))
    groups = _ops.em_groups(d, f32)
    assert groups == {
        8: ((0, 8, 16),), 16: ((0, 16, 16),),
        17: ((0, 16, 16), (16, 17, 32)),
        50: ((0, 16, 16), (16, 32, 32), (32, 50, 64)),
        64: ((0, 16, 16), (16, 32, 32), (32, 64, 64)),
        100: ((0, 16, 16), (16, 32, 32), (32, 64, 64), (64, 80, 80),
              (80, 96, 96), (96, 100, 112))}[d]
    wh = _ops.em_whitener(*(jnp.asarray(a, jnp.float32)
                            for a in (log_w, means, prec)), "full")
    k8 = k + -k % 8
    assert [(r.shape, r.dtype) for r in wh.proj] \
        == [((6 * c, (e1 - e0) * k8), jnp.bfloat16) for e0, e1, c in groups]
    assert [t.shape for t in wh.t] == [(e1 - e0, k8) for e0, e1, _ in groups]
    cut = _log_prob(x, log_w, means, prec)
    assert cut.shape == (300, k) and np.all(np.abs(cut - want) <= room)
    assert np.array_equal(_log_prob(x, log_w, means, poisoned), cut)
    # not the same program: another order of the same sums
    assert d <= 16 or not np.array_equal(cut, whole)


@pytest.mark.parametrize("devices", [1, 8])
def test_fit_score_and_predict_through_the_cut_e_step(monkeypatch, packing,
                                                      devices):
    """A fit whose factors have two groups of columns (d = 20), on blocks
    of 512 rows: ``fit``, ``score`` and ``predict`` run the one cut
    E-step, counted once a trace of ``_gm_fit``, and agree with the plain
    reference's."""
    d = 20
    monkeypatch.setattr(_ops, "_EM_TILE_BYTES", 4 * K * (d + 4) * 512)
    _mesh_of(devices)
    assert len(_ops.em_groups(d, np.dtype(np.float32))) == 2
    rng = np.random.RandomState(21)
    mu = 4.0 * rng.rand(K, d)
    a = np.eye(d) + 0.3 * rng.randn(K, d, d) / np.sqrt(d)
    comp = rng.randint(0, K, ROWS)
    x = (mu[comp] + np.einsum("bd,bde->be", rng.randn(ROWS, d), a[comp])
         ).astype(np.float32)
    start = (np.full((K,), 1.0 / K, np.float32),
             (mu + 0.3 * rng.randn(K, d)).astype(np.float32),
             np.tile(np.eye(d, dtype=np.float32), (K, 1, 1)))
    profiling.reset_counters()
    gm = GaussianMixture(
        n_components=K, max_iter=5, tol=0.0, weights_init=start[0],
        means_init=start[1], precisions_init=start[2]).fit(ds.array(x))
    assert profiling.schedule_counters()["gm_e_step:triangle"] == 1
    assert "gm_e_step:whole" not in profiling.schedule_counters()
    ref_start = (start[0], start[1], start[2])      # P = I: covariances I
    w, m, c, hist = ref.fit(jnp.asarray(x), ref_start, 5, ROWS)
    gaps = ref.compare(_compared(gm), w, m, c, hist, ref_start, 5)
    assert gaps["first_bound_gap"] < 2e-6 and gaps["bound_gap"] < 2e-6 \
        and gaps["means_gap"] < 2e-5 and gaps["covariances_gap"] < 5e-5, gaps
    xa = ds.array(x)
    want, labels = ref.e_step(jnp.asarray(x), gm.weights_, gm.means_,
                              gm.covariances_, ROWS, "full")
    assert gm.score(xa) == pytest.approx(want, rel=2e-6)
    assert np.array_equal(gm.predict(xa).collect().ravel(), labels)


def _block_computation(text):
    """``(inside, outside)``: the lines of the computation of an HLO text
    that holds the E-step's products, a block's body, and all the others."""
    for block in text.split("\n}\n"):
        if re.search(r" dot\(.*dslib\.gm\.e_step/dslib\.pdot", block):
            return block.splitlines(), text.replace(block, "").splitlines()
    raise AssertionError("no computation holds an E-step product")


def test_the_block_loop_holds_one_product_a_group_and_no_split(packing):
    """The program handed to the compiler, at the cell's widths: a
    block's body holds one E-step product a group, each against an
    operand that comes in as an argument, and nothing there converts to
    bfloat16, or concatenates, anything shaped like a factor's operand:
    the split happens once an iteration, outside the loop."""
    ds.init((1, 1), devices=jax.devices()[:1])
    m, d, k = 4 * 7_680, 50, 16
    assert _ops.em_block(m, d, k) == 7_680
    text = _gm._gm_fit.lower(
        jax.ShapeDtypeStruct((m, d), jnp.float32), (m, d), k, "full", 1e-6,
        0.0, 2, (jax.ShapeDtypeStruct((k,), jnp.float32),
                 jax.ShapeDtypeStruct((k, d), jnp.float32),
                 jax.ShapeDtypeStruct((k, d, d), jnp.float32))
    ).as_text(dialect="hlo", debug_info=True)
    inside, outside = _block_computation(text)
    products = [line for line in inside
                if " dot(" in line and "dslib.gm.e_step" in line]
    groups = _ops.em_groups(d, np.dtype(np.float32))
    assert len(products) == len(groups) == 3
    rights = [f"bf16[{6 * c},{(e1 - e0) * k}]" for e0, e1, c in groups]
    assert rights == ["bf16[96,256]", "bf16[192,256]", "bf16[384,288]"]
    for right, product in zip(rights, products):
        arg = re.search(r" dot\(\S+, (\S+)\)", product).group(1)
        assert [line for line in inside if re.match(
            rf"\s*{re.escape(arg)} = {re.escape(right)}\S* parameter\(",
            line)], (right, product)
    # a block's rows are split in the loop; the factors are not (the
    # M-step's wide operand, 16 p's of 16 components, is as wide as one)
    made = [line for line in inside
            if (" convert(" in line or " concatenate(" in line)
            and "dslib.gm.m_step" not in line]
    assert any(re.search(r"= bf16\[7680,", line) for line in made)
    assert not [line for line in made
                if re.search(r"= bf16\[(\d+,)*(256|288)\]", line)], made
    # ... but once an iteration, outside it
    for right in rights:
        assert [line for line in outside if f"= {right}" in line
                and " parameter(" not in line], right


# -- (b) the shifted one-pass covariance --------------------------------------

def test_one_pass_covariance_survives_means_far_from_the_origin(
        small_blocks):
    """Rows whose means lie 100 sigma from the origin.  The pass sums
    about the old means, so what it takes away is second order in how far
    a mean moved; raw float32 moments, sum r x x^T / n - mu mu^T, take
    1e4 sigma^2 away from 1e4 sigma^2 and lose the covariance's digits."""
    ds.init((1, 1), devices=jax.devices()[:1])
    x, mu = _data(5, offset=100.0 / np.sqrt(D))
    assert np.linalg.norm(mu, axis=1).min() > 100.0
    start, covs0 = _start(mu, "full")
    gm = _fit(x, start, "full", 1)
    # float64 truth of one iteration, by the two-pass definition
    x64 = x.astype(np.float64)
    xr, block = _reference_rows(x)
    w, m, c, _ = ref.fit(xr, (start[0], start[1], covs0), 1, block)
    logp = np.asarray(ref.log_prob(
        xr, jnp.asarray(start[0]), jnp.asarray(start[1]),
        *ref.precisions_chol(jnp.asarray(covs0), "full", D), "full",
        "highest"), np.float64)
    resp = np.exp(logp - logp.max(1, keepdims=True))
    resp /= resp.sum(1, keepdims=True)
    nk = resp.sum(0)
    mean64 = resp.T @ x64 / nk[:, None]
    cov64 = np.stack([
        ((x64 - mean64[j]) * resp[:, j:j + 1]).T @ (x64 - mean64[j]) / nk[j]
        for j in range(K)]) + 1e-6 * np.eye(D)

    def gap(c_):
        return np.linalg.norm(c_ - cov64) / np.linalg.norm(cov64)

    assert gap(gm.covariances_) < 2e-5, gap(gm.covariances_)
    assert gap(c) < 2e-5                      # the centred two-pass one
    assert np.linalg.norm(gm.means_ - mean64) \
        / np.linalg.norm(mean64 - start[1]) < 2e-5
    # raw moments in float32, for the contrast
    x32, r32 = x, resp.astype(np.float32)
    raw = np.stack([
        (x32 * r32[:, j:j + 1]).T @ x32 / np.float32(nk[j])
        - np.outer(mean64[j].astype(np.float32),
                   mean64[j].astype(np.float32)) for j in range(K)])
    assert gap(raw) > 50 * gap(gm.covariances_)


# -- (c) score and predict ----------------------------------------------------

@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("cov_type", COV_TYPES)
def test_score_and_predict_agree_with_the_references_e_step(
        small_blocks, cov_type, devices):
    _mesh_of(devices)
    x, mu = _data(7)
    start, _ = _start(mu, cov_type)
    gm = _fit(x, start, cov_type, 3)
    xa = ds.array(x)
    xr, block = _reference_rows(x)
    want, labels = ref.e_step(xr, gm.weights_, gm.means_, gm.covariances_,
                              block, cov_type)
    assert gm.score(xa) == pytest.approx(want, rel=2e-6)
    got = gm.predict(xa).collect().ravel()
    assert got.shape == (ROWS,) and got.dtype == np.int32
    # a row may change sides where its two best components tie to the
    # last bits; none does on this data
    assert np.array_equal(got, labels)


# -- (d) no (m, k) and no (k, m, d) buffer ------------------------------------

def test_the_compiled_fit_holds_no_array_of_the_rows_times_k():
    """1M x 50, k = 16 on the CPU backend: the compiled fit's temporaries
    stay under X's own 200 MB (a block's tiles), where the whole-array
    step held two (k, m, d) arrays of 3.2 GB, 16 times X."""
    ds.init((1, 1), devices=jax.devices()[:1])
    m, d, k = 1_000_000, 50, 16
    x = jax.ShapeDtypeStruct((m, d), jnp.float32)
    start = (jax.ShapeDtypeStruct((k,), jnp.float32),
             jax.ShapeDtypeStruct((k, d), jnp.float32),
             jax.ShapeDtypeStruct((k, d, d), jnp.float32))
    mem = _gm._gm_fit.lower(x, (m, d), k, "full", 1e-6, 0.0, 10,
                            start).compile().memory_analysis()
    if mem is None:
        pytest.skip("backend reports no memory analysis")
    assert mem.temp_size_in_bytes < m * d * 4, mem.temp_size_in_bytes
    # and a start made in the program (a seeded draw) adds none either
    mem = _gm._gm_fit.lower(
        x, (m, d), k, "full", 1e-6, 0.0, 10,
        start={"key": jax.ShapeDtypeStruct((2,), jnp.uint32)}
    ).compile().memory_analysis()
    assert mem.temp_size_in_bytes < m * d * 4, mem.temp_size_in_bytes


# -- (e) the counter and the spans --------------------------------------------

def test_the_counter_and_the_spans_of_one_fit():
    x, mu = _data(9, rows=2000)
    start, _ = _start(mu, "full")
    jax.clear_caches()                  # so that this fit traces
    profiling.reset_counters()
    _fit(x, start, "full", 4)
    c = profiling.counters()
    assert c["schedules"]["gm_step:blocked"] == c["trace_by"]["gm_fit"] == 1
    # this backend packs nothing: the E-step's product is ONE whole GEMM,
    # the M-step's XLA's six passes
    assert c["schedules"]["gm_e_step:whole"] == 1
    assert "gm_e_step:triangle" not in c["schedules"]
    assert c["schedules"]["gm_m_step:six_pass"] == 1
    assert "gm_m_step:packed" not in c["schedules"]
    assert c["schedules"]["gm_m_moments:whole"] == 1
    assert "gm_m_moments:upper" not in c["schedules"]
    spans = c["spans"]
    for name in ("dslib.gm.fit", "dslib.gm.init", "dslib.fitloop.run",
                 "dslib.fitloop.chunk", "dslib.fitloop.commit"):
        assert spans[name]["count"] == 1, name
    # the health vector, the history and the three parameters
    assert spans["dslib.host_read"]["count"] == c["transfers"] == 5
    assert c["dispatch_by"] == {"gm_fit": 1}
    assert spans["dslib.gm.fit"]["total_s"] \
        >= spans["dslib.fitloop.run"]["total_s"] \
        >= spans["dslib.gm.init"]["total_s"]
    # a second fit of the same shapes traces nothing and bumps nothing
    _fit(x, start, "full", 4)
    assert profiling.schedule_counters()["gm_step:blocked"] == 1
    assert profiling.schedule_counters()["gm_e_step:whole"] == 1
    assert profiling.schedule_counters()["gm_m_step:six_pass"] == 1
    assert profiling.schedule_counters()["gm_m_moments:whole"] == 1


@pytest.mark.parametrize("cov_type", ["tied", "diag", "spherical"])
def test_the_m_step_counter_counts_full_covariances_only(packing, cov_type):
    """The other covariance types have no product to pack nor moments to
    mirror: their fit says nothing of either, packing backend or not; a
    full fit there says ``packed`` and ``upper``, once a trace."""
    ds.init((1, 1), devices=jax.devices()[:1])
    x, mu = _data(9, rows=2000)
    profiling.reset_counters()
    _fit(x, _start(mu, cov_type)[0], cov_type, 2)
    assert not [key for key in profiling.schedule_counters()
                if key.startswith(("gm_m_step:", "gm_m_moments:"))]
    # nor a triangle to cut: their E-step is whole, packed or not
    assert profiling.schedule_counters()["gm_e_step:whole"] == 1
    for _ in range(2):
        _fit(x, _start(mu, "full")[0], "full", 2)
    assert profiling.schedule_counters()["gm_m_step:packed"] == 1
    assert "gm_m_step:six_pass" not in profiling.schedule_counters()
    assert profiling.schedule_counters()["gm_m_moments:upper"] == 1
    assert "gm_m_moments:whole" not in profiling.schedule_counters()
    assert profiling.schedule_counters()["gm_e_step:triangle"] == 1
    assert profiling.schedule_counters()["gm_e_step:whole"] == 1


@pytest.mark.parametrize("scope", ["dslib.gm.chol", "dslib.gm.e_step",
                                   "dslib.gm.m_step", "dslib.gm.close",
                                   "dslib.gm.pass", "dslib.pdot"])
def test_device_scope_is_in_an_op_name(scope):
    x = ds.random_array((64, D), random_state=0)
    text = profiling.op_graph(
        lambda xp, w, mu, c: _gm._gm_fit(xp, x.shape, K, "full", 1e-6, 0.0,
                                         2, (w, mu, c)),
        x._data, jnp.full((K,), 1.0 / K), jnp.ones((K, D)),
        jnp.tile(jnp.eye(D), (K, 1, 1)))
    assert f"/{scope}/" in text and 'op_name="' in text, scope


# -- (f) the start ------------------------------------------------------------

def test_explicit_starts_run_no_kmeans_and_default_starts_do():
    x, mu = _data(11, rows=2000)
    start, _ = _start(mu, "full")
    profiling.reset_counters()
    _fit(x, start, "full", 2)
    c = profiling.counters()
    assert set(c["dispatch_by"]) == {"gm_fit"}
    assert "dslib.kmeans.init_centers" not in c["spans"]
    profiling.reset_counters()
    gm = GaussianMixture(n_components=K, max_iter=2, tol=0.0,
                         random_state=0).fit(ds.array(x))
    c = profiling.counters()
    assert {"kmeans_fit", "kmeans_predict", "gm_fit"} <= set(c["dispatch_by"])
    assert np.all(np.isfinite(gm.means_))
    # one of the three left out is made by the start and the rest kept
    profiling.reset_counters()
    gm = GaussianMixture(n_components=K, max_iter=1, tol=0.0, random_state=0,
                         means_init=start[1]).fit(ds.array(x))
    assert "kmeans_fit" in profiling.counters()["dispatch_by"]


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("cov_type", COV_TYPES)
def test_a_random_start_is_a_normalised_draw_made_block_by_block(
        small_blocks, cov_type, devices):
    """``init_params='random'``: responsibilities uniform and normalised
    over the components, drawn a block at a time.  Every component then
    weighs about 1/k and sits near the rows' mean, on any mesh; the fit
    from it runs and its bound does not fall."""
    _mesh_of(devices)
    x, _ = _data(13)
    gm = GaussianMixture(n_components=K, covariance_type=cov_type,
                         init_params="random", max_iter=1, tol=0.0,
                         random_state=4).fit(ds.array(x))
    # after ONE iteration from the drawn start the parameters are still
    # those of nearly equal components around the rows' mean
    assert np.allclose(gm.weights_, 1.0 / K, atol=0.05)
    assert np.abs(gm.means_ - x.mean(0)).max() < 0.5
    more = GaussianMixture(n_components=K, covariance_type=cov_type,
                           init_params="random", max_iter=8, tol=0.0,
                           random_state=4).fit(ds.array(x))
    assert np.all(np.diff(more.history_) > -1e-4)
    assert more.history_[0] == pytest.approx(gm.history_[0], rel=1e-6)
