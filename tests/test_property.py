"""Property-based hardening of the ds-array core (hypothesis).

The reference's most bug-catching tests are irregular-shape slicing and
mixed elementwise/reduction cases (SURVEY §5); here hypothesis drives the
same surface with randomized shapes, block sizes, slices and fancy indices
against the NumPy oracle.  Deadlines are disabled (first jit trace of a new
shape dominates wall time).

Round-8 satellite: on rigs WITHOUT the hypothesis package (it lives in the
``dev`` extra) the tier no longer skips silently — `_hypothesis_lite`
supplies deterministic seeded sampling for the same properties at a
smaller example budget (no shrinking; install hypothesis for the full
search)."""

import numpy as np
import pytest  # noqa: F401 — fixture plumbing

try:
    from hypothesis import given, settings, strategies as st
    _LITE = False
except ImportError:
    from _hypothesis_lite import given, settings, strategies as st
    _LITE = True

import dislib_tpu as ds  # noqa: E402

# A DSLIB_TEST_TPU=1 run is one file per chip call under that call's time
# limit, so it keeps the same properties at sample size 5 — the
# hardware-rounding check — while the CPU rig keeps the full search.
import os

# lite tier runs the TPU smoke budget: it is the always-on smoke pass of
# this tier (tier-1 wall-clock is budgeted), not the full search
_N = 5 if os.environ.get("DSLIB_TEST_TPU") == "1" else (5 if _LITE else 25)
_settings = settings(max_examples=_N, deadline=None)


@st.composite
def arr_and_block(draw):
    m = draw(st.integers(1, 40))
    n = draw(st.integers(1, 17))
    br = draw(st.integers(1, 40))
    bc = draw(st.integers(1, 17))
    seed = draw(st.integers(0, 2**16))
    data = np.random.RandomState(seed).standard_normal((m, n)) \
        .astype(np.float32)
    return data, (br, bc)


@given(arr_and_block())
@_settings
def test_roundtrip_and_reductions(ab):
    data, bs = ab
    x = ds.array(data, block_size=bs)
    np.testing.assert_allclose(np.asarray(x.collect()), data, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(x.sum(axis=0).collect()).ravel(),
                               data.sum(0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(x.mean(axis=1).collect()).ravel(),
                               data.mean(1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(x.min(axis=0).collect()).ravel(),
                               data.min(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(x.max(axis=1).collect()).ravel(),
                               data.max(1), rtol=1e-5, atol=1e-6)


@given(arr_and_block(), st.data())
@_settings
def test_slicing_matches_numpy(ab, payload):
    data, bs = ab
    m, n = data.shape
    x = ds.array(data, block_size=bs)
    r0 = payload.draw(st.integers(0, m - 1))
    r1 = payload.draw(st.integers(r0 + 1, m))
    c0 = payload.draw(st.integers(0, n - 1))
    c1 = payload.draw(st.integers(c0 + 1, n))
    got = np.asarray(x[r0:r1, c0:c1].collect())
    np.testing.assert_allclose(got, data[r0:r1, c0:c1], rtol=1e-6)
    # fancy row indexing
    k = payload.draw(st.integers(1, m))
    idx = payload.draw(st.lists(st.integers(0, m - 1), min_size=k,
                                max_size=k))
    got = np.asarray(x[idx, :].collect())
    np.testing.assert_allclose(got, data[idx, :], rtol=1e-6)


@given(arr_and_block(), st.integers(0, 2**16))
@_settings
def test_elementwise_and_transpose(ab, seed2):
    data, bs = ab
    other = np.random.RandomState(seed2).standard_normal(data.shape) \
        .astype(np.float32)
    x = ds.array(data, block_size=bs)
    y = ds.array(other, block_size=bs)
    np.testing.assert_allclose(np.asarray((x + y).collect()), data + other,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray((x * y).collect()), data * other,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray((x - y).collect()), data - other,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(x.T.collect()), data.T, rtol=1e-6)
    # transpose round-trip keeps the pad-and-mask invariant intact
    np.testing.assert_allclose(np.asarray(x.T.T.collect()), data, rtol=1e-6)


@given(st.integers(0, 2**16), st.integers(5, 30), st.integers(3, 12))
@_settings
def test_sparse_roundtrip_and_ops(seed, m, n):
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    rng = np.random.RandomState(seed)
    dense = rng.rand(m, n).astype(np.float32)
    dense[dense < 0.6] = 0.0
    xs = SparseArray.from_scipy(sp.csr_matrix(dense))
    np.testing.assert_allclose(np.asarray(xs.collect().toarray()), dense,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(xs.sum(axis=0).collect()).ravel(),
                               dense.sum(0), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(xs.square().collect().toarray()), dense ** 2, rtol=1e-5)
    got = (xs + xs._scaled(-1.0)).collect().toarray()
    np.testing.assert_allclose(got, np.zeros_like(dense), atol=1e-6)
    np.testing.assert_allclose(np.asarray(xs.T.collect().toarray()), dense.T,
                               rtol=1e-6)


@given(st.integers(0, 2**16), st.integers(1, 400), st.integers(1, 64),
       st.floats(0.02, 0.9))
@_settings
def test_row_steps_invariants(seed, m, chunk, density):
    """row_steps (kNN sparse streaming) invariants for arbitrary sparsity
    patterns: steps partition [0, m) in order, every step respects the row
    cap, every nonzero lands exactly once with correct local coordinates,
    and the rectangle memory stays within the documented budget bound."""
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    rng = np.random.RandomState(seed)
    dense = (rng.rand(m, 8) < density).astype(np.float32) * rng.rand(m, 8)
    xs = SparseArray.from_scipy(sp.csr_matrix(dense))
    data, lrows, cols, row_off, rows_in = (np.asarray(a) for a in
                                           xs.row_steps(chunk))
    # partition: contiguous, ordered, covers all m rows exactly once
    covered = 0
    for ro, rc in zip(row_off, rows_in):
        assert ro == covered
        assert 0 <= rc <= chunk
        covered += int(rc)
    assert covered == m
    # reconstruction: scatter every step back and compare
    rebuilt = np.zeros_like(dense)
    for s in range(data.shape[0]):
        np.add.at(rebuilt, (row_off[s] + lrows[s], cols[s]), data[s])
        assert (lrows[s] < max(1, rows_in[s])).all()
    np.testing.assert_allclose(rebuilt, dense, rtol=1e-6)
    # memory bound: the per-step nnz budget itself obeys the documented
    # formula (4x the average chunk's nonzeros, floored at 64 and at the
    # densest single row) — a regression to budget = O(densest chunk)
    # would fail this
    row_nnz = (dense != 0).sum(axis=1)
    want = max(64, 4 * int(np.ceil(xs.nnz * chunk / max(m, 1))),
               int(row_nnz.max(initial=1)))
    assert data.shape[1] <= want


@given(st.integers(0, 2**16), st.integers(1, 9), st.integers(1, 8))
@_settings
def test_tsqr_invariants(seed, n, mult):
    """QᵀQ≈I and QR≈A across tall shapes, including ones that engage the
    batched-tree local QR (rows ≫ n) and ones that pad shards (rows < p·n)."""
    m = n * mult * 8 + (seed % 7)           # sometimes ragged vs the mesh
    if m < n:
        m = n
    x = np.random.RandomState(seed).standard_normal((m, n)).astype(np.float32)
    q, r = ds.tsqr(ds.array(x))
    qc, rc = q.collect(), r.collect()
    assert qc.shape == (m, n) and rc.shape == (n, n)
    np.testing.assert_allclose(qc @ rc, x, atol=5e-4 * max(1, np.abs(x).max()))
    np.testing.assert_allclose(qc.T @ qc, np.eye(n), atol=5e-4)
    assert np.allclose(rc, np.triu(rc))


@given(st.integers(0, 2**16), st.integers(1, 60), st.integers(1, 12),
       st.floats(0.05, 0.9))
@_settings
def test_ell_invariants(seed, m, n, density):
    """ELL buffers densify back to the exact matrix (round-4 CSVM staging
    representation), padding entries contribute nothing, and the budget
    guard trips exactly on the padded byte size."""
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.classification.csvm import _ell_rows_dense
    import jax.numpy as jnp
    mat = sp.random(m, n, density=density, random_state=seed,
                    dtype=np.float32).tocsr()
    sa = SparseArray.from_scipy(mat)
    ell = sa.ell()
    assert ell is not None
    ev, ec = ell
    assert ev.shape == ec.shape and ev.shape[0] == m
    dense = np.asarray(_ell_rows_dense(ev, ec, jnp.arange(m), n))
    np.testing.assert_allclose(dense, mat.toarray(), rtol=1e-6, atol=1e-7)
    # row-nnz bound: r is exactly the max row nnz (no silent inflation)
    row_nnz = np.diff(mat.indptr)
    assert ev.shape[1] == max(1, int(row_nnz.max(initial=1)))
    # budget guard: one byte below the need → fallback (fresh object: the
    # cache also re-checks, but this pins the fresh-build path)
    need = m * ev.shape[1] * 8
    sa2 = SparseArray.from_scipy(mat)
    assert sa2.ell(budget=need - 1) is None
    assert sa2.ell(budget=need) is not None


@given(st.integers(0, 2**16), st.integers(1, 60), st.integers(1, 12),
       st.floats(0.05, 0.9))
@_settings
def test_sharded_rows_invariants(seed, m, n, density):
    """The rectangular row-sharded representation reconstructs the exact
    matrix: every nonzero lands in its shard's bucket at its local row,
    padding entries are zero-valued, and rowsq matches per-row ‖·‖²."""
    import scipy.sparse as sp
    from dislib_tpu.data.sparse import SparseArray
    from dislib_tpu.parallel import mesh as _mesh
    mat = sp.random(m, n, density=density, random_state=seed,
                    dtype=np.float32).tocsr()
    sa = SparseArray.from_scipy(mat)
    mesh = _mesh.get_mesh()
    p = mesh.shape[_mesh.ROWS]
    data, lrows, cols, rowsq = (np.asarray(a) for a in sa.sharded_rows())
    m_local = -(-m // p)
    rebuilt = np.zeros((p * m_local, n), np.float32)
    for s in range(p):
        np.add.at(rebuilt, (s * m_local + lrows[s], cols[s]), data[s])
    np.testing.assert_allclose(rebuilt[:m], mat.toarray(), rtol=1e-6,
                               atol=1e-7)
    assert not rebuilt[m:].any(), "padding rows carry mass"
    dense = mat.toarray()
    np.testing.assert_allclose(
        rowsq.reshape(-1)[: m], (dense * dense).sum(1), rtol=1e-5,
        atol=1e-6)
