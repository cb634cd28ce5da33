"""The sparse ALS fit's blocked normal equations (recommendation/als.py):
users and items cut into length classes, each class laid out once in
windows, a block's Grams one batched product, users solved a block at a
time, items' products added over the shards by one psum.

Checked against a float64 oracle of the textbook half-steps on seeded
ratings with heavy-tailed rows, a user with no rating, a user and an item
whose entries span several pieces and blocks, and an item every user
rated; on one device and on two (the psum path).  Block sizes are shrunk
through the module's constants so that a small fit is cut as a big one
is."""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

import dislib_tpu as ds
from dislib_tpu.data.sparse import ShardedSparse, SparseArray
from dislib_tpu.recommendation import ALS
from dislib_tpu.recommendation import als as als_mod
from dislib_tpu.utils import profiling


def _ratings(seed=0, m=260, n=40):
    """Heavy-tailed rows (1 to all n entries), user 7 with none, item 3
    rated by every user, item 11 by none; integer ratings 1 to 5."""
    rng = np.random.default_rng(seed)
    counts = np.clip(np.exp(rng.normal(np.log(6), 1.1, m)), 1, n).astype(int)
    r = np.zeros((m, n), np.float32)
    for u, c in enumerate(counts):
        r[u, rng.choice(n, size=c, replace=False)] = rng.integers(1, 6, c)
    r[:, 3] = rng.integers(1, 6, m)
    r[7] = 0
    r[:, 11] = 0
    return r


def _half(r, src, lam):
    f = src.shape[1]
    out = np.zeros((r.shape[0], f))
    for i in range(r.shape[0]):
        o = r[i] != 0
        vo = src[o].astype(np.float64)
        a = vo.T @ vo + lam * max(o.sum(), 1) * np.eye(f)
        out[i] = np.linalg.solve(a, vo.T @ r[i, o])
    return out


def _oracle(r, v, lam, iters):
    hist = []
    for _ in range(iters):
        u = _half(r, v, lam)
        v = _half(r.T, u, lam)
        err = (u @ v.T - r)[r != 0]
        hist.append(np.sqrt((err ** 2).mean()))
    return u, v, hist


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of at most 32 segments and 256 slots, pieces of 16 entries:
    the 260 users fill several blocks of several classes, item 3's 260
    entries are 17 pieces, and the longest user's window is 3 pieces."""
    monkeypatch.setattr(als_mod, "_PIECE", 16)
    monkeypatch.setattr(als_mod, "_BLOCK_ENTRIES", 256)
    monkeypatch.setattr(als_mod, "_BLOCK_SEGMENTS", 32)
    jax.clear_caches()
    yield
    jax.clear_caches()


# (devices, dense share): the module's share (nearly every item of
# _ratings goes dense), one above 1 (none does: every item is gathered)
# and a quarter (14 items, item 3 among them, beside 25 gathered)
_FITS = {"one_device": (1, None), "psum_of_two": (2, None),
         "one_device-gathered": (1, 2.0), "psum_of_two-gathered": (2, 2.0),
         "one_device-quarter": (1, 0.25), "psum_of_two-quarter": (2, 0.25)}


@pytest.mark.parametrize("rows, share", list(_FITS.values()), ids=list(_FITS))
def test_blocked_fit_matches_the_float64_oracle(small_blocks, monkeypatch,
                                                rows, share):
    if share is not None:
        monkeypatch.setattr(als_mod, "_DENSE_SHARE", share)
    ds.init((rows, 1), devices=jax.devices()[:rows])
    r = _ratings()
    f, lam = 5, 0.1
    v0 = np.random.default_rng(1).random((r.shape[1], f)).astype(np.float32)
    profiling.reset_counters()
    als = ALS(n_f=f, lambda_=lam, max_iter=3, tol=0,
              items_init=v0).fit(SparseArray.from_scipy(sp.csr_matrix(r)))
    u, v, hist = _oracle(r, v0, lam, 3)
    assert als.n_iter_ == 3
    np.testing.assert_allclose(als.users_, u, rtol=0, atol=2e-4)
    np.testing.assert_allclose(als.items_, v, rtol=0, atol=2e-4)
    np.testing.assert_allclose(als.history_, hist, rtol=2e-5)
    assert als.rmse_ == pytest.approx(hist[-1], rel=2e-5)
    assert not als.users_[7].any() and not als.items_[11].any()
    counters = profiling.schedule_counters()
    assert counters["als_normal:grouped"] >= 1
    dense = (r != 0).sum(axis=0) >= als_mod._DENSE_SHARE * r.shape[0]
    assert dense[3] != (share == 2.0)
    assert share != 0.25 or 3 < dense.sum() < r.shape[1] // 2
    route = "dense" if dense.any() else "gathered"
    assert counters[f"als_items:{route}"] >= 1
    assert not counters.get("als_items:" + ({"dense", "gathered"} - {
        route}).pop())


@pytest.mark.parametrize("rows", [1, 2], ids=["one_device", "two_shards"])
def test_every_rating_sits_once_in_a_window_or_the_dense_part(
        small_blocks, monkeypatch, rows):
    """The items rated by at least the share of the users are exactly the
    dense ones, their segments are in no class of the item layout, and
    every rating sits once in a window or in the dense ratings, with the
    plan's counts and sums of squares beside them."""
    monkeypatch.setattr(als_mod, "_DENSE_SHARE", 0.25)
    ds.init((rows, 1), devices=jax.devices()[:rows])
    r = _ratings()
    rows_, cols_ = np.nonzero(r)
    rep = ShardedSparse.build(rows_, cols_, r[rows_, cols_], r.shape)
    _, ic, _, items = als_mod._plans(rep)
    want = np.flatnonzero((r != 0).sum(axis=0) >= 0.25 * r.shape[0])
    assert 3 < want.size < r.shape[1] // 2 and 3 in want
    seg_ids, _, others, vals, dense = items
    ids, seen, sq, dr = (np.asarray(a) for a in dense)
    for s in range(rows):
        np.testing.assert_array_equal(ids[s], want)
    seg_ids = np.asarray(seg_ids).reshape(rows, -1)
    ml = rep.m_local
    got = []
    for k, (size, b, blocks, seg_off) in enumerate(ic):
        o = np.asarray(others[k]).reshape(rows, blocks * b, size)
        v = np.asarray(vals[k]).reshape(rows, blocks * b, size)
        for s in range(rows):
            seg = seg_ids[s, seg_off:seg_off + blocks * b]
            assert not np.isin(seg, want).any()
            for j, item in enumerate(seg):
                live = v[s, j] != 0
                assert (o[s, j, ~live] == ml).all()
                got += [(s * ml + int(lr), int(item), float(x))
                        for lr, x in zip(o[s, j, live], v[s, j, live])]
    dr = dr.reshape(rows, want.size, ml)
    for s in range(rows):
        k, lr = np.nonzero(dr[s])
        got += [(s * ml + int(a), int(want[c]), float(dr[s, c, a]))
                for c, a in zip(k, lr)]
        mine = r[s * ml:(s + 1) * ml][:, want]
        np.testing.assert_array_equal(seen[s], (mine != 0).sum(axis=0))
        np.testing.assert_allclose(sq[s], (mine.astype(np.float64) ** 2)
                                   .sum(axis=0), rtol=1e-6)
    assert sorted(got) == sorted(zip(rows_.tolist(), cols_.tolist(),
                                     r[rows_, cols_].tolist()))


def test_the_plan_covers_every_segment_once_in_its_class(small_blocks):
    counts = np.array([[0, 1, 16, 17, 300, 5, 0], [3, 0, 40, 2, 2, 2, 1]])
    classes, tab = als_mod._segment_plan(counts)
    seen = {0: [], 1: []}
    for size, b, blocks, seg_off in classes:
        for s in (0, 1):
            ids, first, length = tab[s, :, seg_off:seg_off + b * blocks]
            live = ids < counts.shape[1]
            assert (length[live] <= size).all()
            assert (length[~live] == 0).all()
            seen[s] += ids[live].tolist()
            assert (first[live] == (np.cumsum(counts[s]) - counts[s])[
                ids[live]]).all()
    for s in (0, 1):
        assert sorted(seen[s]) == np.flatnonzero(counts[s]).tolist()
    sizes = [c[0] for c in classes]
    assert sizes == sorted(sizes) and sizes[-1] >= 300
    assert all(size % 16 == 0 for size in sizes if size > 16)


@pytest.mark.parametrize("longest", [1, 2048, 2049, 5000, 700_000])
def test_the_long_classes_step_by_whole_pieces(longest):
    """Past one piece the windows are 2, 3, 4, 6, 8, 12, ... pieces, up to
    the first two that hold the longest segment."""
    sizes = als_mod._class_sizes(longest)
    assert sizes[-1] >= longest and sizes[-3] < longest or len(sizes) == 11
    long = [s // als_mod._PIECE for s in sizes if s >= als_mod._PIECE]
    assert long == [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192,
                    256, 384, 512][:len(long)]


def test_col_major_sorts_each_shard_by_column(rng):
    ds.init((2, 1), devices=jax.devices()[:2])
    r = _ratings(seed=3)
    rows, cols = np.nonzero(r)
    rep = ShardedSparse.build(rows, cols, r[rows, cols], r.shape)
    lr, vals = (np.asarray(a) for a in rep.col_major())
    cc = rep.col_counts()
    for s in range(2):
        mine = (rows // rep.m_local) == s
        order = np.lexsort((rows[mine], cols[mine]))
        k = int(mine.sum())
        assert cc[s].sum() == k
        np.testing.assert_array_equal(lr[s, :k],
                                      (rows[mine] - s * rep.m_local)[order])
        np.testing.assert_array_equal(vals[s, :k], r[rows, cols][mine][order])
        assert not vals[s, k:].any()
        np.testing.assert_array_equal(cc[s], np.bincount(cols[mine],
                                                         minlength=r.shape[1]))


def test_the_lane_solve_is_the_xla_solve(small_blocks, monkeypatch):
    """The chip's route (a system a lane, pallas_kernels.chol_solve_lanes,
    interpreted here) gives the fit the CPU's XLA Cholesky gives, both
    solving the products of the windowed items' route."""
    monkeypatch.setattr(als_mod, "_DENSE_SHARE", 2.0)
    r = _ratings(seed=5, m=150)
    v0 = np.random.default_rng(2).random((r.shape[1], 6)).astype(np.float32)
    x = SparseArray.from_scipy(sp.csr_matrix(r))
    xla = ALS(n_f=6, lambda_=0.05, max_iter=2, tol=0, items_init=v0).fit(x)
    monkeypatch.setattr(als_mod, "_LANE_BACKENDS", (jax.default_backend(),))
    jax.clear_caches()
    profiling.reset_counters()
    lanes = ALS(n_f=6, lambda_=0.05, max_iter=2, tol=0, items_init=v0).fit(x)
    assert profiling.schedule_counters()["als_solve:lanes"] == 1
    np.testing.assert_allclose(lanes.users_, xla.users_, rtol=0, atol=1e-5)
    np.testing.assert_allclose(lanes.items_, xla.items_, rtol=0, atol=1e-5)


def test_items_init_is_the_start_on_both_paths():
    r = _ratings(seed=7, m=60, n=20)
    v0 = np.random.default_rng(4).random((20, 3)).astype(np.float32)
    u, v, _ = _oracle(r, v0, 0.1, 1)
    sparse = ALS(n_f=3, lambda_=0.1, max_iter=1, tol=0, items_init=v0).fit(
        SparseArray.from_scipy(sp.csr_matrix(r)))
    dense = ALS(n_f=3, lambda_=0.1, max_iter=1, tol=0, items_init=v0).fit(
        ds.array(r))
    for got in (sparse, dense):
        np.testing.assert_allclose(got.users_, u, rtol=0, atol=1e-4)
        np.testing.assert_allclose(got.items_, v, rtol=0, atol=1e-4)
    with pytest.raises(ValueError, match="items_init"):
        ALS(n_f=4, items_init=v0).fit(SparseArray.from_scipy(
            sp.csr_matrix(r)))


def test_the_compiled_fit_holds_no_users_by_f_squared(monkeypatch):
    """The fit compiled at 50 000 users x 400 items, 1.3M entries, f = 16:
    its temporaries stay under the entry stream's 15.5 MB, where one
    (users, f, f) table of normal equations alone would take 51 MB."""
    monkeypatch.setattr(als_mod, "_BLOCK_ENTRIES", 8192)
    monkeypatch.setattr(als_mod, "_BLOCK_SEGMENTS", 256)
    m, n, f = 50_000, 400, 16
    rng = np.random.default_rng(0)
    counts = np.clip(np.exp(rng.normal(np.log(16), 1.0, m)), 1, n)
    counts = counts.astype(np.int64)
    nnz = int(counts.sum())
    rows = np.repeat(np.arange(m), counts)
    cols = (rng.integers(0, n, nnz) + np.arange(nnz)) % n
    rep = ShardedSparse.build(rows, cols, np.ones(nnz, np.float32), (m, n))
    uc, ic, users, items = als_mod._plans(rep)
    test = (rep.data, rep.lrows, rep.cols, rep.counts_dev)
    start = als_mod._als_start(None, 0, rep.p * rep.m_local, n, f, rep.mesh)
    mem = als_mod._als_fit_sparse.lower(
        users, items, test, (*start, np.inf), n, 0.065, 0.0, 3, 4,
        rep.mesh, uc, ic, False).compile().memory_analysis()
    assert nnz * 12 < 16e6
    assert mem.temp_size_in_bytes < nnz * 12, mem.temp_size_in_bytes
    assert m * f * f * 4 > 3 * nnz * 12


def test_the_compiled_dense_pass_holds_no_users_by_packed_outer(monkeypatch):
    """The fit compiled at 50 000 users x 400 items, f = 16, with 12 items
    that every second user rated: the dense pass's temporaries hold a
    piece of users' packed outer products at a time, and no (users, f (f
    + 1) / 2) or (users, dense items, f) array, each of which alone would
    take more than all the temporaries."""
    monkeypatch.setattr(als_mod, "_BLOCK_ENTRIES", 8192)
    monkeypatch.setattr(als_mod, "_BLOCK_SEGMENTS", 256)
    ds.init((1, 1), devices=jax.devices()[:1])
    m, n, f, h = 50_000, 400, 16, 12
    rng = np.random.default_rng(1)
    counts = np.clip(np.exp(rng.normal(np.log(16), 1.0, m)), 1, n - h)
    counts = counts.astype(np.int64)
    rows = np.repeat(np.arange(m), counts)
    cols = h + (rng.integers(0, n - h, rows.size) + np.arange(rows.size)) \
        % (n - h)
    heavy = rng.random((m, h)) < 0.5
    hr, hc = np.nonzero(heavy)
    rows, cols = np.concatenate([rows, hr]), np.concatenate([cols, hc])
    rep = ShardedSparse.build(rows, cols, np.ones(rows.size, np.float32),
                              (m, n))
    uc, ic, users, items = als_mod._plans(rep)
    assert np.asarray(items[4][0]).tolist() == [list(range(h))]
    test = (rep.data, rep.lrows, rep.cols, rep.counts_dev)
    start = als_mod._als_start(None, 0, rep.p * rep.m_local, n, f, rep.mesh)
    compiled = als_mod._als_fit_sparse.lower(
        users, items, test, (*start, np.inf), n, 0.065, 0.0, 3, 4,
        rep.mesh, uc, ic, False).compile()
    packed = f * (f + 1) // 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < m * packed * 4 and temp < m * h * f * 4, temp
    text = compiled.as_text()
    assert f"[{h},{rep.m_local}]" in text      # the dense ratings, whole
    ml, width = rep.m_local, als_mod._packed_index(f)[1]
    for shape in (f"[{ml},{packed}]", f"[{packed},{ml}]", f"[{ml},{width}]",
                  f"[{width},{ml}]", f"[{ml},{h},{f}]", f"[{ml},{f},{h}]",
                  f"[{h},{ml},{f}]"):
        assert shape not in text, shape
