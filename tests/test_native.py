"""Native (C++) fastio layer: parity with the pure-NumPy parsers.

The native module is a performance component with a mandatory fallback, so
these tests assert BOTH that the native parse (when buildable) matches the
NumPy oracle and that the io.py entry points give identical results with the
native layer disabled (DSLIB_NO_NATIVE)."""

import io as _io
import os

import numpy as np
import pytest

from dislib_tpu import native


def _native_available():
    return native.get_lib() is not None


pytestmark = pytest.mark.skipif(
    not _native_available(), reason="native toolchain unavailable (fallback "
    "paths are covered by tests/test_io.py)")


class TestParseText:
    def test_matches_loadtxt(self):
        rng = np.random.RandomState(0)
        a = rng.standard_normal((500, 13)).astype(np.float64)
        buf = "\n".join(",".join(f"{v:.9e}" for v in row) for row in a)
        buf = buf.encode()
        got = native.parse_text(buf)
        ref = np.loadtxt(_io.BytesIO(buf), delimiter=",", dtype=np.float32,
                         ndmin=2)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, rtol=2e-7, atol=1e-30)

    def test_plain_decimals_and_blank_lines(self):
        buf = b"1.5,2,-3.25\n\n4,5.125,6\n   \n7,8,9\n"
        got = native.parse_text(buf)
        np.testing.assert_allclose(
            got, [[1.5, 2, -3.25], [4, 5.125, 6], [7, 8, 9]])

    def test_inf_nan_fallback_tokens(self):
        got = native.parse_text(b"1.0,inf,-inf\nnan,2.5e-3,3\n")
        assert np.isinf(got[0, 1]) and got[0, 1] > 0
        assert np.isinf(got[0, 2]) and got[0, 2] < 0
        assert np.isnan(got[1, 0])
        np.testing.assert_allclose(got[1, 1:], [2.5e-3, 3.0])

    def test_ragged_raises(self):
        with pytest.raises(native.NativeUnavailable):
            native.parse_text(b"1,2,3\n4,5\n")

    def test_malformed_token_raises(self):
        # np.loadtxt raises on these; the native layer must defer, not guess
        with pytest.raises(native.NativeUnavailable):
            native.parse_text(b"a1,2\n3,4\n")
        with pytest.raises(native.NativeUnavailable):
            native.parse_text(b"1,,3\n")          # empty field
        with pytest.raises(native.NativeUnavailable):
            native.parse_text(b"1,2,\n")          # trailing delimiter

    def test_comments_match_loadtxt(self):
        buf = b"# h1,h2\n1,2 # trailing\n3,4\n"
        got = native.parse_text(buf)
        ref = np.loadtxt(_io.BytesIO(buf), delimiter=",", dtype=np.float32,
                         ndmin=2)
        np.testing.assert_array_equal(got, ref)

    def test_empty(self):
        assert native.parse_text(b"").shape == (0, 0)

    def test_threaded_equals_single(self):
        rng = np.random.RandomState(1)
        a = rng.rand(997, 7).astype(np.float32)   # odd row count: uneven split
        buf = "\n".join(",".join(f"{v:.6f}" for v in row) for row in a)
        buf = buf.encode()
        np.testing.assert_array_equal(native.parse_text(buf, nthreads=1),
                                      native.parse_text(buf, nthreads=5))


class TestParseSvmlight:
    def test_csr_roundtrip(self):
        sv = b"1 1:0.5 3:2.0\n-1 2:1.5\n# comment line\n1 1:1.0 4:2.5e-1\n"
        labels, indptr, indices, data, nfeat = native.parse_svmlight(sv)
        np.testing.assert_allclose(labels, [1, -1, 1])
        assert nfeat == 4
        import scipy.sparse as sp
        csr = sp.csr_matrix((data, indices, indptr), shape=(3, nfeat))
        dense = csr.toarray()
        np.testing.assert_allclose(dense[0], [0.5, 0, 2.0, 0])
        np.testing.assert_allclose(dense[1], [0, 1.5, 0, 0])
        np.testing.assert_allclose(dense[2], [1.0, 0, 0, 0.25])

    def test_malformed_raises(self):
        with pytest.raises(native.NativeUnavailable):
            native.parse_svmlight(b"1 nonsense\n")

    def test_duplicate_indices_sum_both_paths(self, tmp_path):
        p = str(tmp_path / "dup.svm")
        with open(p, "w") as f:
            f.write("1 2:1.0 2:2.0\n-1 1:0.5\n")
        from dislib_tpu.data.io import load_svmlight_file
        x1, _ = load_svmlight_file(p, store_sparse=False)
        os.environ["DSLIB_NO_NATIVE"] = "1"
        try:
            x2, _ = load_svmlight_file(p, store_sparse=False)
        finally:
            del os.environ["DSLIB_NO_NATIVE"]
        np.testing.assert_allclose(x1.collect(), x2.collect())
        assert np.asarray(x1.collect())[0, 1] == 3.0   # 1.0 + 2.0 summed


class TestParseMdcrdErrors:
    def test_overflow_field_raises(self):
        # AMBER writes ******** on overflow; dropping the field would shift
        # every later coordinate — must defer to the Python path (raises)
        buf = b"title\n   1.000********   3.000\n"
        with pytest.raises(native.NativeUnavailable):
            native.parse_mdcrd(buf)


class TestParseMdcrd:
    def test_fixed_width(self):
        vals = np.arange(24, dtype=np.float32) * 1.5
        body = "".join(f"{v:8.3f}" for v in vals)
        lines = "\n".join(body[i:i + 80] for i in range(0, len(body), 80))
        buf = ("title\n" + lines + "\n").encode()
        got = native.parse_mdcrd(buf)
        np.testing.assert_allclose(got, vals, atol=1e-3)


class TestIoIntegration:
    """io.py entry points: native and fallback paths agree."""

    def test_load_txt_file_paths_agree(self, tmp_path):
        rng = np.random.RandomState(2)
        a = rng.rand(64, 5).astype(np.float32)
        p = str(tmp_path / "m.csv")
        np.savetxt(p, a, delimiter=",")
        from dislib_tpu.data.io import load_txt_file
        x_native = load_txt_file(p, block_size=(16, 5)).collect()
        os.environ["DSLIB_NO_NATIVE"] = "1"
        try:
            x_py = load_txt_file(p, block_size=(16, 5)).collect()
        finally:
            del os.environ["DSLIB_NO_NATIVE"]
        np.testing.assert_allclose(x_native, x_py, rtol=1e-6)

    def test_load_svmlight_paths_agree(self, tmp_path):
        p = str(tmp_path / "d.svm")
        with open(p, "w") as f:
            f.write("1 1:0.5 3:2.0\n-1 2:1.5\n1 1:1.0 4:0.25\n2 3:1.0\n")
        from dislib_tpu.data.io import load_svmlight_file
        x1, y1 = load_svmlight_file(p, store_sparse=False)
        os.environ["DSLIB_NO_NATIVE"] = "1"
        try:
            x2, y2 = load_svmlight_file(p, store_sparse=False)
        finally:
            del os.environ["DSLIB_NO_NATIVE"]
        np.testing.assert_allclose(x1.collect(), x2.collect(), rtol=1e-6)
        np.testing.assert_allclose(y1.collect(), y2.collect(), rtol=1e-6)


class TestBuildFailureIsRemembered:
    def test_reason_is_kept_and_numpy_parsers_serve(self, monkeypatch,
                                                    tmp_path):
        """A failed build leaves the NumPy parsers serving — and says
        why, instead of vanishing into a silent fallback."""
        import subprocess

        import dislib_tpu as ds

        def broken():
            raise subprocess.CalledProcessError(
                1, ["g++"], stderr=b"fastio.cpp:1: error: no such thing")
        monkeypatch.setattr(native, "_build_and_load", broken)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_build_error", None)
        assert native.get_lib() is None
        assert "g++ exited 1" in native.build_error()
        assert "no such thing" in native.build_error()
        p = tmp_path / "x.csv"
        p.write_text("1,2\n3,4\n")
        np.testing.assert_allclose(
            ds.load_txt_file(str(p), block_size=(2, 2)).collect(),
            [[1, 2], [3, 4]])
