"""Native host-side fast paths with pure-Python fallback.

``fastpath.cpp`` is compiled on demand with the system C++ toolchain into a
CPython extension (no pybind11 needed). If compilation is unavailable the
same API is served by numpy/pure-Python implementations, so the package has
no hard native dependency — but never in silence: :func:`build_error` says
why, and ``chip_smoke.py`` fails on it. Mirrors the reference's NativeLoader
pattern (``core/.../core/env/NativeLoader.java``) of shipping a loadable
native payload behind a stable interface.

API:
    available() -> bool
    build_error() -> str | None     (why available() is False)
    murmur3(data: bytes, seed: int) -> int
    murmur3_batch(seq_of_bytes, seed, mask) -> np.uint32[n]
    pad_sparse(rows, K) -> (np.int32[n,K], np.float32[n,K])
    stack_rows(seq_of_float_vectors, d) -> np.float32[n,d]
    bin_columns(X, bounds, lengths, want_u16) -> np.uint8/uint16[n,F]
"""

from __future__ import annotations

import os
import subprocess
import sys
import sysconfig

import numpy as np

__all__ = ["available", "bin_columns", "build_error", "murmur3",
           "murmur3_batch", "pad_sparse", "parse_libsvm", "stack_rows"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "fastpath.cpp")
_SO = os.path.join(_HERE, f"_fastpath{sysconfig.get_config_var('EXT_SUFFIX')}")

_impl = None
_build_error = None


def _compile() -> bool:
    """Build the extension in place; returns success. A failure's reason
    (the compiler's own words) is kept for :func:`build_error`."""
    global _build_error
    include_py = sysconfig.get_paths()["include"]
    include_np = np.get_include()
    # build to a unique temp name, then atomically publish: concurrent
    # importers on a shared filesystem never see a half-written .so
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
           f"-I{include_py}", f"-I{include_np}", _SRC, "-o", tmp]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        _build_error = f"{type(e).__name__}: {e}"
        return False
    if res.returncode != 0 or not os.path.exists(tmp):
        _build_error = (f"g++ exited {res.returncode}: "
                        f"{res.stderr.strip()[-2000:]}")
        return False
    os.replace(tmp, _SO)
    return True


def _load():
    global _impl, _build_error
    if _impl is not None:
        return _impl
    if os.environ.get("MMLSPARK_TPU_NO_NATIVE") == "1":
        _build_error = "disabled by MMLSPARK_TPU_NO_NATIVE=1"
        _impl = False
        return _impl
    # a shipped .so without the source is fine — only rebuild when the
    # source exists and is newer than the binary
    usable = os.path.exists(_SO) and (
        not os.path.exists(_SRC)
        or os.path.getmtime(_SO) >= os.path.getmtime(_SRC))
    if not usable:
        if not os.path.exists(_SRC):
            _build_error = f"neither {_SO} nor {_SRC} exists"
            _impl = False
            return _impl
        if not _compile():
            _impl = False
            return _impl
    try:
        sys.path.insert(0, _HERE)
        import _fastpath  # noqa
        _impl = _fastpath
    except ImportError as e:
        _build_error = f"import of {_SO} failed: {e}"
        _impl = False
    finally:
        if _HERE in sys.path:
            sys.path.remove(_HERE)
    return _impl


def available() -> bool:
    return bool(_load())


def build_error():
    """Why :func:`available` is False (the compiler's stderr, a missing
    source, the opt-out knob), or None when the fast path loaded."""
    _load()
    return _build_error


# -- dispatching wrappers ----------------------------------------------------

def murmur3(data: bytes, seed: int = 0) -> int:
    impl = _load()
    if impl:
        return impl.murmur3(data, seed & 0xFFFFFFFF)
    from ..vw.murmur import _murmur3_32_py
    return _murmur3_32_py(data, seed)


def murmur3_batch(items, seed: int, mask: int) -> np.ndarray:
    impl = _load()
    if impl:
        return impl.murmur3_batch(list(items), seed & 0xFFFFFFFF, mask)
    from ..vw.murmur import _murmur3_32_py
    return np.asarray([_murmur3_32_py(b, seed) & mask for b in items],
                      dtype=np.uint32)


def pad_sparse(rows, K: int):
    impl = _load()
    if impl:
        return impl.pad_sparse(list(rows), int(K))
    n = len(rows)
    idx = np.zeros((n, K), dtype=np.int32)
    val = np.zeros((n, K), dtype=np.float32)
    for i, (ri, rv) in enumerate(rows):
        ri = np.asarray(ri)
        rv = np.asarray(rv)
        k = min(len(ri), len(rv), K)   # clamp like the native path
        idx[i, :k] = ri[:k].astype(np.int64)
        val[i, :k] = rv[:k]
    return idx, val


def parse_libsvm(data: bytes):
    """LightGBM-style libsvm text → CSR pieces:
    (labels f64[n], qids i64[n] (-1 = absent), indptr i64[n+1],
    indices i32[nnz], values f32[nnz])."""
    impl = _load()
    if impl:
        return impl.parse_libsvm(bytes(data))
    labels, qids, indices, values = [], [], [], []
    indptr = [0]
    for line in bytes(data).decode("utf-8", "replace").splitlines():
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        toks = line.split()
        labels.append(float(toks[0]))
        qid = -1
        for t in toks[1:]:
            k, _, v = t.partition(":")
            if not _:
                raise ValueError(f"libsvm: bad feature token {t!r}")
            if k == "qid":
                qid = int(v)
                continue
            ki = int(k)
            if not (0 <= ki <= 0x7FFFFFFF):
                # match the native parser: no silent int32 wraparound
                raise ValueError(f"libsvm: feature index {ki} out of "
                                 "int32 range")
            indices.append(ki)
            values.append(float(v))
        qids.append(qid)
        indptr.append(len(indices))
    return (np.asarray(labels, np.float64), np.asarray(qids, np.int64),
            np.asarray(indptr, np.int64), np.asarray(indices, np.int32),
            np.asarray(values, np.float32))


def bin_columns(X: np.ndarray, bounds: np.ndarray, lengths: np.ndarray,
                want_u16: bool) -> np.ndarray:
    """Quantile-bin a float matrix: ``searchsorted(bounds_j, x, "left") + 1``
    per element with NaN → bin 0. ``bounds`` is the (F, L) padded table,
    ``lengths`` the per-feature bound counts. The native loop replaces 28
    per-column ``np.searchsorted`` passes — the dataset-construction cost
    LightGBM pays in C++ (``LGBM_DatasetCreateFromMat``)."""
    impl = _load()
    if impl:
        return impl.bin_columns(np.ascontiguousarray(X), bounds, lengths,
                                int(bool(want_u16)))
    n, f = X.shape
    dtype = np.uint16 if want_u16 else np.uint8
    out = np.zeros((n, f), dtype=dtype)
    is_float = X.dtype.kind == "f"
    for j in range(f):
        col = X[:, j]
        binned = np.searchsorted(bounds[j, :lengths[j]], col,
                                 side="left") + 1
        if is_float:
            binned = np.where(np.isnan(col), 0, binned)
        out[:, j] = binned.astype(dtype)
    return out


def stack_rows(rows, d: int) -> np.ndarray:
    impl = _load()
    if impl:
        return impl.stack_rows(list(rows), int(d))
    out = np.zeros((len(rows), d), dtype=np.float32)
    for i, r in enumerate(rows):
        a = np.asarray(r, dtype=np.float32).ravel()
        k = min(len(a), d)
        out[i, :k] = a[:k]
    return out
