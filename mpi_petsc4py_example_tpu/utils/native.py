"""ctypes loader for the native C++ CSR toolkit (native/csrkit.cpp).

Compiles the shared library on first use (g++ -O3 -march=native) into
``native/build/``, under a name keyed on a hash of the source, the flags
and this host's CPU features: a library built from other source, with
other flags or on another CPU (a working tree copied to another machine)
is never loaded — it is rebuilt. Every entry point has a vectorized-numpy
fallback, so the framework works without a toolchain; a failed build or
load warns once and :func:`status` says which path runs. The native path
matters for large operators (100M-DoF assembly) where Python-level passes
dominate.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
import warnings

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "native", "csrkit.cpp")
_BUILD_DIR = os.path.join(_REPO, "native", "build")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")

_lock = threading.Lock()
_lib = None
_lib_tried = False
_status = "not tried"

_I64 = ctypes.POINTER(ctypes.c_int64)
_I32 = ctypes.POINTER(ctypes.c_int32)
_F64 = ctypes.POINTER(ctypes.c_double)


def _as(arr, ptr_t):
    return arr.ctypes.data_as(ptr_t)


def build_key(src: bytes) -> str:
    """Hash of the source, the compiler flags and this host's CPU
    features — the name a matching library is built under."""
    from .aot import host_machine_fingerprint
    h = hashlib.sha256(src)
    h.update(" ".join(_FLAGS).encode())
    h.update(host_machine_fingerprint().encode())
    return h.hexdigest()[:16]


def _compile() -> str:
    """Path of the library built from this source, with these flags, for
    this host — building it if absent. Raises on a failed build."""
    with open(_SRC, "rb") as fh:
        so = os.path.join(_BUILD_DIR,
                          f"libcsrkit-{build_key(fh.read())}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, suffix=".so.tmp")
    os.close(fd)
    try:
        subprocess.run(["g++", *_FLAGS, "-o", tmp, _SRC], check=True,
                       capture_output=True, timeout=120)
        os.replace(tmp, so)      # atomic: concurrent builders agree
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return so


def get_lib():
    """The loaded native library, or None if unavailable."""
    global _lib, _lib_tried, _status
    with _lock:
        if _lib is None and not _lib_tried:
            _lib_tried = True
            try:
                so = _compile()
                lib = ctypes.CDLL(so)
                lib.csr_validate.restype = ctypes.c_int
                lib.csr_max_row_nnz.restype = ctypes.c_int64
                lib.csr_aggregate.restype = ctypes.c_int64
                _lib = lib
                _status = f"loaded {os.path.basename(so)}"
            except (subprocess.SubprocessError, OSError,
                    AttributeError) as e:
                _status = f"numpy fallback ({type(e).__name__}: {e})"
                warnings.warn(f"native CSR toolkit unavailable, using the "
                              f"numpy path: {e}", RuntimeWarning,
                              stacklevel=2)
        return _lib


def available() -> bool:
    return get_lib() is not None


def status() -> str:
    """Which path the CSR toolkit takes: ``loaded <lib>`` or ``numpy
    fallback (<why>)``."""
    get_lib()
    return _status


def _prep(indptr, indices, data):
    return (np.ascontiguousarray(indptr, dtype=np.int64),
            np.ascontiguousarray(indices, dtype=np.int32),
            np.ascontiguousarray(data, dtype=np.float64))


def csr_validate(indptr, indices, ncols: int) -> int:
    """0 if the CSR triple is well-formed, else a negative error code."""
    indptr, indices, _ = (np.ascontiguousarray(indptr, dtype=np.int64),
                          np.ascontiguousarray(indices, dtype=np.int32),
                          None)
    nrows = len(indptr) - 1
    lib = get_lib()
    if lib is not None:
        return int(lib.csr_validate(_as(indptr, _I64), nrows,
                                    _as(indices, _I32), len(indices),
                                    ctypes.c_int64(ncols)))
    if indptr[0] != 0:
        return -1
    if (np.diff(indptr) < 0).any():
        return -2
    if indptr[-1] != len(indices):
        return -3
    if len(indices) and (indices.min() < 0 or indices.max() >= ncols):
        return -4
    return 0


def csr_to_ell_native(indptr, indices, data, nrows_pad: int | None = None):
    """CSR -> ELL via the native kernel (numpy fallback in ops.spmv)."""
    indptr, indices, data = _prep(indptr, indices, data)
    nrows = len(indptr) - 1
    lib = get_lib()
    if lib is None:
        from ..ops.spmv import csr_to_ell
        cols, vals = csr_to_ell(indptr, indices, data)
        return cols, vals
    K = max(int(lib.csr_max_row_nnz(_as(indptr, _I64), nrows)), 1)
    cols = np.zeros((nrows, K), dtype=np.int32)
    vals = np.zeros((nrows, K), dtype=np.float64)
    lib.csr_to_ell(_as(indptr, _I64), _as(indices, _I32), _as(data, _F64),
                   ctypes.c_int64(nrows), ctypes.c_int64(K),
                   _as(cols, _I32), _as(vals, _F64))
    return cols, vals


def csr_slice_rows_native(indptr, indices, data, rstart: int, rend: int):
    """Rebased row-block slice via the native kernel."""
    indptr, indices, data = _prep(indptr, indices, data)
    lib = get_lib()
    if lib is None:
        from ..parallel.partition import slice_csr_block
        return slice_csr_block(indptr, indices, data, rstart, rend)
    nloc = rend - rstart
    nnz = int(indptr[rend] - indptr[rstart])
    lp = np.empty(nloc + 1, dtype=np.int64)
    li = np.empty(nnz, dtype=np.int32)
    ld = np.empty(nnz, dtype=np.float64)
    lib.csr_slice_rows(_as(indptr, _I64), _as(indices, _I32),
                       _as(data, _F64), ctypes.c_int64(rstart),
                       ctypes.c_int64(rend), _as(lp, _I64), _as(li, _I32),
                       _as(ld, _F64))
    return lp, li, ld


def csr_diagonal_native(indptr, indices, data, n: int):
    indptr, indices, data = _prep(indptr, indices, data)
    lib = get_lib()
    if lib is None:
        from ..ops.spmv import csr_diag
        return csr_diag(indptr, indices, data, n)
    diag = np.empty(n, dtype=np.float64)
    lib.csr_diagonal(_as(indptr, _I64), _as(indices, _I32), _as(data, _F64),
                     ctypes.c_int64(n), _as(diag, _F64))
    return diag


def csr_aggregate_native(indptr, indices):
    """Greedy (Vanek) aggregation over a CSR strength pattern.

    Returns ``(agg, nagg)``. Falls back to the Python reference loop in
    solvers.amg when no toolchain is available.
    """
    indptr = np.ascontiguousarray(indptr, dtype=np.int64)
    indices = np.ascontiguousarray(indices, dtype=np.int32)
    nrows = len(indptr) - 1
    lib = get_lib()
    if lib is None:
        return None
    agg = np.empty(nrows, dtype=np.int64)
    nagg = int(lib.csr_aggregate(_as(indptr, _I64), _as(indices, _I32),
                                 ctypes.c_int64(nrows), _as(agg, _I64)))
    return agg, nagg


def csr_spmv_native(indptr, indices, data, x):
    """Host-side oracle SpMV (debug/verification)."""
    indptr, indices, data = _prep(indptr, indices, data)
    x = np.ascontiguousarray(x, dtype=np.float64)
    nrows = len(indptr) - 1
    lib = get_lib()
    if lib is None:
        import scipy.sparse as sp
        n_cols = len(x)
        return sp.csr_matrix((data, indices, indptr),
                             shape=(nrows, n_cols)) @ x
    y = np.empty(nrows, dtype=np.float64)
    lib.csr_spmv(_as(indptr, _I64), _as(indices, _I32), _as(data, _F64),
                 ctypes.c_int64(nrows), _as(x, _F64), _as(y, _F64))
    return y
