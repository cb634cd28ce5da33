"""Native (C++) host-side kernels — lazy build + ctypes bindings.

`fastio.cpp` is compiled on first use with the in-image g++ into
`_fastio-<tag>.so` next to this file (tag = compiler/source hash so a source
edit triggers a rebuild).  Every entry point returns None / raises
`NativeUnavailable` cleanly when the toolchain or the parse is unusable, and
callers in `dislib_tpu.data.io` fall back to the pure-NumPy path — the
native layer is a performance component, never a correctness dependency.
Why a build or load failed is kept: :func:`build_error` returns it.

Set ``DSLIB_NO_NATIVE=1`` to disable entirely (forces the NumPy paths).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastio.cpp")
_lock = threading.Lock()
_lib = None
_tried = False
_build_error: str | None = None


class NativeUnavailable(RuntimeError):
    pass


def _build_and_load():
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(_HERE, f"_fastio-{tag}.so")
    if not os.path.exists(so):
        tmp = so + f".tmp{os.getpid()}"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
               _SRC, "-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)          # atomic: concurrent builders race safely
    lib = ctypes.CDLL(so)

    i64 = ctypes.c_int64
    pi64 = ctypes.POINTER(i64)
    pf32 = ctypes.POINTER(ctypes.c_float)
    lib.fastio_parse_text.restype = pf32
    lib.fastio_parse_text.argtypes = [ctypes.c_char_p, i64, ctypes.c_char,
                                      ctypes.c_int, pi64, pi64]
    lib.fastio_parse_svmlight.restype = ctypes.c_int
    lib.fastio_parse_svmlight.argtypes = [
        ctypes.c_char_p, i64, ctypes.POINTER(pf32), ctypes.POINTER(pi64),
        ctypes.POINTER(pi64), ctypes.POINTER(pf32), pi64, pi64]
    lib.fastio_parse_mdcrd.restype = pf32
    lib.fastio_parse_mdcrd.argtypes = [ctypes.c_char_p, i64, pi64]
    lib.fastio_free.restype = None
    lib.fastio_free.argtypes = [ctypes.c_void_p]
    return lib


def get_lib():
    """The loaded native library, or None if unavailable/disabled (the
    NumPy parsers are the documented alternative; :func:`build_error`
    says why the native one is absent)."""
    global _lib, _tried, _build_error
    if os.environ.get("DSLIB_NO_NATIVE"):
        return None
    with _lock:
        if not _tried:
            _tried = True
            try:
                _lib = _build_and_load()
            except subprocess.CalledProcessError as e:   # g++ rejected it
                tail = (e.stderr or b"").decode(errors="replace")[-400:]
                _build_error = f"g++ exited {e.returncode}: {tail.strip()}"
            except (OSError, subprocess.TimeoutExpired,
                    AttributeError) as e:
                # no g++ / unwritable dir / unloadable .so / build past
                # 120 s / a .so missing an entry point
                _build_error = f"{type(e).__name__}: {e}"
    return _lib


def build_error() -> str | None:
    """Why the native library is absent after :func:`get_lib` returned
    None (``None`` while it loaded, was never tried, or is disabled by
    ``DSLIB_NO_NATIVE``)."""
    return _build_error


def _take(lib, ptr, count, dtype):
    """Copy `count` elements out of a native buffer, then free it."""
    arr = np.ctypeslib.as_array(ptr, shape=(count,)).astype(dtype, copy=True)
    lib.fastio_free(ptr)
    return arr


def parse_text(buf: bytes, delimiter: str = ",", nthreads: int | None = None):
    """Parse delimited text → float32 (rows, cols) ndarray, or raise
    NativeUnavailable (caller falls back to np.loadtxt)."""
    lib = get_lib()
    if lib is None:
        raise NativeUnavailable
    if nthreads is None:
        nthreads = min(os.cpu_count() or 1, 16)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    ptr = lib.fastio_parse_text(buf, len(buf),
                                delimiter.encode()[:1] or b",",
                                nthreads, ctypes.byref(rows),
                                ctypes.byref(cols))
    if rows.value < 0:
        raise NativeUnavailable("ragged rows — deferring to np.loadtxt")
    if not ptr:
        return np.zeros((0, 0), np.float32)
    flat = _take(lib, ptr, rows.value * cols.value, np.float32)
    return flat.reshape(rows.value, cols.value)


def parse_svmlight(buf: bytes):
    """Parse svmlight text → (labels, indptr, indices, data, n_features) in
    CSR form, or raise NativeUnavailable."""
    lib = get_lib()
    if lib is None:
        raise NativeUnavailable
    pf32 = ctypes.POINTER(ctypes.c_float)
    pi64 = ctypes.POINTER(ctypes.c_int64)
    labels_p, data_p = pf32(), pf32()
    indptr_p, indices_p = pi64(), pi64()
    nrows = ctypes.c_int64()
    nfeat = ctypes.c_int64()
    rc = lib.fastio_parse_svmlight(buf, len(buf),
                                   ctypes.byref(labels_p),
                                   ctypes.byref(indptr_p),
                                   ctypes.byref(indices_p),
                                   ctypes.byref(data_p),
                                   ctypes.byref(nrows), ctypes.byref(nfeat))
    if rc != 0:
        for p in (labels_p, indptr_p, indices_p, data_p):
            if p:
                lib.fastio_free(p)
        raise NativeUnavailable("malformed svmlight — deferring to Python")
    n = nrows.value
    if n == 0:
        for p in (labels_p, indptr_p, indices_p, data_p):
            if p:
                lib.fastio_free(p)
        return (np.zeros(0, np.float32), np.zeros(1, np.int64),
                np.zeros(0, np.int64), np.zeros(0, np.float32), 0)
    labels = _take(lib, labels_p, n, np.float32)
    indptr = _take(lib, indptr_p, n + 1, np.int64)
    nnz = int(indptr[-1])
    indices = _take(lib, indices_p, nnz, np.int64)
    data = _take(lib, data_p, nnz, np.float32)
    return labels, indptr, indices, data, int(nfeat.value)


def parse_mdcrd(buf: bytes):
    """Parse AMBER mdcrd body → flat float32 values, or raise
    NativeUnavailable."""
    lib = get_lib()
    if lib is None:
        raise NativeUnavailable
    nvals = ctypes.c_int64()
    ptr = lib.fastio_parse_mdcrd(buf, len(buf), ctypes.byref(nvals))
    if nvals.value < 0:
        raise NativeUnavailable("mdcrd allocation failure")
    if not ptr:
        return np.zeros(0, np.float32)
    return _take(lib, ptr, nvals.value, np.float32)
