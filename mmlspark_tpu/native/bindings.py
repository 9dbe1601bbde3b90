"""ctypes surface of libmmlspark_native.so + numpy fallbacks."""

from __future__ import annotations

import contextlib
import ctypes
import fcntl
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

from mmlspark_tpu.core import sanitizer
from mmlspark_tpu.core.faults import fault_point
from mmlspark_tpu.core.logging_utils import logger

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libmmlspark_native.so")

_lock = sanitizer.san_lock("native.build")
_lib: Optional[ctypes.CDLL] = None
_build_failed = False
_building = False
_build_done = threading.Event()
_quant_symbols = False


@contextlib.contextmanager
def _build_flock():
    """One PROCESS at a time builds and loads: an exclusive ``flock`` on
    a file beside the target, held across ``make`` and the load. Test
    workers and fit processes that start together on a fresh checkout
    otherwise all run ``make`` at once, and one loads (or has mapped) a
    library another is still writing. Where the directory cannot be
    written nothing can be built there either, and no lock is taken."""
    try:
        fh = open(os.path.join(_NATIVE_DIR, ".build.lock"), "a")
    except OSError:
        yield
        return
    with fh:                      # closing the file drops the lock
        fcntl.flock(fh, fcntl.LOCK_EX)
        yield


def ensure_built() -> bool:
    """Compile the shared library if missing; returns availability.

    The compile (make, up to 120s) runs OUTSIDE ``_lock``: one caller
    is elected builder under the lock, concurrent callers park on
    ``_build_done`` — holding a lock across a subprocess would stall
    every thread that merely wants the cached availability answer
    (GL012, blocking-under-lock). Across processes ``_build_flock``
    serialises the build and the load, and ``make`` renames a finished
    library onto the target, so no process ever maps a partial file.
    A load the loader refused is not remembered: the next call tries
    again."""
    global _lib, _build_failed, _building
    with _lock:
        if _lib is not None:
            return True
        if _build_failed:
            return False
        if _building:
            elected = False
        else:
            _building = True
            _build_done.clear()
            elected = True
    if not elected:
        # another thread is compiling: wait for its verdict (bounded
        # well past the make timeout so a crashed builder can't park
        # us forever), then read the published result
        _build_done.wait(timeout=300)
        with _lock:
            return _lib is not None
    lib: Optional[ctypes.CDLL] = None
    failed = load_again = False
    try:
        with _build_flock():
            # always run make: it is a no-op when the .so is fresh and
            # rebuilds when data_plane.cpp is newer (a stale library
            # would silently miss symbols added since it was built)
            try:
                subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                               capture_output=True, timeout=120)
            except Exception as e:
                if not os.path.exists(_SO_PATH):
                    logger.warning("native build failed (%s); using "
                                   "numpy fallbacks", e)
                    failed = True
                else:
                    logger.warning("native rebuild failed (%s); loading "
                                   "the existing library", e)
            if not failed:
                try:
                    loaded = ctypes.CDLL(_SO_PATH)
                except OSError as e:
                    logger.warning("native load failed (%s); numpy "
                                   "fallbacks for this call", e)
                    load_again = True
                else:
                    _configure(loaded)
                    lib = loaded    # published only once configured
    finally:
        with _lock:
            _lib = lib
            _build_failed = lib is None and not load_again
            _building = False
        _build_done.set()
    return lib is not None


def _configure(lib: ctypes.CDLL) -> None:
    i64 = ctypes.c_int64
    lib.mmls_murmur3_32.restype = ctypes.c_uint32
    lib.mmls_murmur3_32.argtypes = [ctypes.c_char_p, i64, ctypes.c_uint32]
    lib.mmls_murmur3_batch.restype = None
    lib.mmls_murmur3_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(i64), i64, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_uint32)]
    lib.mmls_bin_matrix.restype = None
    lib.mmls_bin_matrix.argtypes = [
        ctypes.POINTER(ctypes.c_double), i64, i64,
        ctypes.POINTER(ctypes.c_double), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    lib.mmls_csv_dims.restype = ctypes.c_int
    lib.mmls_csv_dims.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                  ctypes.POINTER(i64), ctypes.POINTER(i64)]
    lib.mmls_csv_parse.restype = ctypes.c_int
    lib.mmls_csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                   ctypes.POINTER(ctypes.c_double), i64, i64]
    lib.mmls_libsvm_dims.restype = i64
    lib.mmls_libsvm_dims.argtypes = [ctypes.c_char_p, ctypes.POINTER(i64),
                                     ctypes.POINTER(i64)]
    lib.mmls_libsvm_parse.restype = ctypes.c_int
    lib.mmls_libsvm_parse.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_double), i64, i64]
    f32p = ctypes.POINTER(ctypes.c_float)
    i32 = ctypes.c_int32
    for name, binp in (("mmls_level_hist_u8",
                        ctypes.POINTER(ctypes.c_uint8)),
                       ("mmls_level_hist_i32", ctypes.POINTER(i32))):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [binp, i64, i64, f32p, f32p, f32p,
                       ctypes.POINTER(i32), i32, i32, f32p]
    global _quant_symbols
    u8p = ctypes.POINTER(ctypes.c_uint8)
    _quant_symbols = True
    for name, binp, qp in (
            ("mmls_level_hist_q16_u8", u8p,
             ctypes.POINTER(ctypes.c_int16)),
            ("mmls_level_hist_q16_i32", ctypes.POINTER(i32),
             ctypes.POINTER(ctypes.c_int16)),
            ("mmls_level_hist_q8_u8", u8p,
             ctypes.POINTER(ctypes.c_int8)),
            ("mmls_level_hist_q8_i32", ctypes.POINTER(i32),
             ctypes.POINTER(ctypes.c_int8))):
        try:
            fn = getattr(lib, name)
        except AttributeError:
            # stale pre-built .so from before the quantized kernels
            # landed (rebuild failed): keep the f32 surface usable
            _quant_symbols = False
            break
        fn.restype = None
        fn.argtypes = [binp, i64, i64, qp, qp, u8p,
                       ctypes.POINTER(i32), i32, i32,
                       ctypes.c_float, ctypes.c_float, f32p]


def is_available() -> bool:
    return ensure_built()


# ---------------------------------------------------------------------------
# public ops (native when available, numpy otherwise)
# ---------------------------------------------------------------------------

def murmur3_batch(strings, seed: int = 0) -> np.ndarray:
    """uint32 murmur3 of each string."""
    if ensure_built():
        blob = b"".join(s.encode() if isinstance(s, str) else bytes(s)
                        for s in strings)
        offsets = np.zeros(len(strings) + 1, np.int64)
        pos = 0
        for i, s in enumerate(strings):
            pos += len(s.encode() if isinstance(s, str) else s)
            offsets[i + 1] = pos
        out = np.zeros(len(strings), np.uint32)
        _lib.mmls_murmur3_batch(
            blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(strings), seed,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)))
        return out
    from mmlspark_tpu.ops.hashing import murmur3_32
    return np.asarray([murmur3_32(s, seed) for s in strings], np.uint32)


def bin_matrix(vals: np.ndarray, uppers: np.ndarray) -> np.ndarray:
    """(n, f) doubles -> int32 bin ids via (f, B) upper edges."""
    vals = np.ascontiguousarray(vals, np.float64)
    uppers = np.ascontiguousarray(uppers, np.float64)
    n, f = vals.shape
    n_bins = uppers.shape[1]
    if ensure_built():
        out = np.zeros((n, f), np.int32)
        _lib.mmls_bin_matrix(
            vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n, f,
            uppers.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n_bins,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out
    out = np.empty((n, f), np.int32)
    for j in range(f):
        out[:, j] = np.minimum(
            np.searchsorted(uppers[j], vals[:, j], side="left"), n_bins - 1)
    return out


def level_histogram(binned: np.ndarray, grad: np.ndarray,
                    hess: np.ndarray, live: np.ndarray,
                    local: np.ndarray, width: int,
                    n_bins: int) -> np.ndarray:
    """GBDT per-level histogram: (n, f) bin ids + per-row stats ->
    (width, f, n_bins, 3) float32 grad/hess/count sums, accumulated as
    ``(grad*live, hess*live, live)`` into the row's ``local`` node.

    The cache-blocked C++ kernel when the library is available (row
    order within a worker chunk, worker chunks merged in order — the
    float sum order is deterministic for a given thread count); a
    bincount fallback otherwise. Bin ids must be < ``n_bins`` and
    ``local`` in [0, width) — the trainer's binning/clipping guarantees
    both.
    """
    n, f = binned.shape
    grad = np.ascontiguousarray(grad, np.float32)
    hess = np.ascontiguousarray(hess, np.float32)
    live = np.ascontiguousarray(live, np.float32)
    local = np.ascontiguousarray(local, np.int32)
    if ensure_built():
        if binned.dtype == np.uint8:
            binned = np.ascontiguousarray(binned)
            fn, binp = _lib.mmls_level_hist_u8, ctypes.c_uint8
        else:
            binned = np.ascontiguousarray(binned, np.int32)
            fn, binp = _lib.mmls_level_hist_i32, ctypes.c_int32
        out = np.empty((width, f, n_bins, 3), np.float32)
        fn(binned.ctypes.data_as(ctypes.POINTER(binp)), n, f,
           grad.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
           hess.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
           live.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
           local.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
           width, n_bins,
           out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        # injection point on the histogram RESULT: arming corrupt here
        # proves a bad data-plane answer changes the model (so parity
        # tests really exercise this kernel); delay simulates a slow one
        return sanitizer.check_dtype_contract(
            "gbdt.level_hist", sanitizer.check_finite(
                "gbdt.level_hist",
                fault_point("gbdt.level_hist", out)))
    out = np.zeros((width, f, n_bins, 3), np.float32)
    if n == 0:
        return sanitizer.check_dtype_contract(
            "gbdt.level_hist", sanitizer.check_finite(
                "gbdt.level_hist",
                fault_point("gbdt.level_hist", out)))
    idx_base = local.astype(np.int64) * n_bins
    chans = (grad * live, hess * live, live)
    for j in range(f):
        idx = idx_base + binned[:, j]
        for c, w in enumerate(chans):
            out[:, j, :, c] = np.bincount(
                idx, weights=w, minlength=width * n_bins
            ).reshape(width, n_bins).astype(np.float32)
    return sanitizer.check_dtype_contract(
        "gbdt.level_hist", sanitizer.check_finite(
            "gbdt.level_hist",
            fault_point("gbdt.level_hist", out)))


def quant_histogram_available() -> bool:
    """True when the loaded library exports the quantized kernels."""
    return ensure_built() and _quant_symbols


def level_histogram_quant(binned: np.ndarray, grad_q: np.ndarray,
                          hess_q: np.ndarray, live: np.ndarray,
                          local: np.ndarray, width: int, n_bins: int,
                          gscale_inv: float, hscale_inv: float
                          ) -> np.ndarray:
    """Quantized GBDT per-level histogram: int16 (or int8) grad/hess
    accumulated into int32 SIMD tiles with periodic folds into exact
    int64 sums, dequantized once at the merge. ``live`` is a 0/1 uint8
    gate. Bit-identical to the int64 bincount fallback below for any
    worker count because the inverse scales are powers of two (the
    single f32 rounding step happens after the exact integer sum).
    """
    n, f = binned.shape
    qdt = np.int8 if grad_q.dtype == np.int8 else np.int16
    grad_q = np.ascontiguousarray(grad_q, qdt)
    hess_q = np.ascontiguousarray(hess_q, qdt)
    live = np.ascontiguousarray(live, np.uint8)
    local = np.ascontiguousarray(local, np.int32)
    if quant_histogram_available():
        if binned.dtype == np.uint8:
            binned = np.ascontiguousarray(binned)
            binp = ctypes.c_uint8
            fn = (_lib.mmls_level_hist_q8_u8 if qdt == np.int8
                  else _lib.mmls_level_hist_q16_u8)
        else:
            binned = np.ascontiguousarray(binned, np.int32)
            binp = ctypes.c_int32
            fn = (_lib.mmls_level_hist_q8_i32 if qdt == np.int8
                  else _lib.mmls_level_hist_q16_i32)
        qp = ctypes.c_int8 if qdt == np.int8 else ctypes.c_int16
        out = np.empty((width, f, n_bins, 3), np.float32)
        fn(binned.ctypes.data_as(ctypes.POINTER(binp)), n, f,
           grad_q.ctypes.data_as(ctypes.POINTER(qp)),
           hess_q.ctypes.data_as(ctypes.POINTER(qp)),
           live.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
           local.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
           width, n_bins, gscale_inv, hscale_inv,
           out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return sanitizer.check_dtype_contract(
            "gbdt.level_hist", sanitizer.check_finite(
                "gbdt.level_hist",
                fault_point("gbdt.level_hist", out)))
    out = np.zeros((width, f, n_bins, 3), np.float32)
    if n == 0:
        return sanitizer.check_dtype_contract(
            "gbdt.level_hist", sanitizer.check_finite(
                "gbdt.level_hist",
                fault_point("gbdt.level_hist", out)))
    gate = live != 0
    idx_base = local.astype(np.int64) * n_bins
    # float64 bincount of integer-valued weights is exact below 2^53,
    # matching the native kernel's int64 accumulators bit-for-bit
    chans = (np.where(gate, grad_q, 0).astype(np.float64),
             np.where(gate, hess_q, 0).astype(np.float64),
             gate.astype(np.float64))
    scales = (np.float64(gscale_inv), np.float64(hscale_inv),
              np.float64(1.0))
    for j in range(f):
        idx = idx_base + binned[:, j]
        for c, (w, s) in enumerate(zip(chans, scales)):
            sums = np.bincount(idx, weights=w,
                               minlength=width * n_bins)
            out[:, j, :, c] = (sums.reshape(width, n_bins)
                               * s).astype(np.float32)
    return sanitizer.check_dtype_contract(
        "gbdt.level_hist", sanitizer.check_finite(
            "gbdt.level_hist",
            fault_point("gbdt.level_hist", out)))


def load_csv(path: str, skip_header: bool = True
             ) -> np.ndarray:
    """Parse a numeric CSV into an (n, f) float64 matrix."""
    if ensure_built():
        i64 = ctypes.c_int64
        rows, cols = i64(), i64()
        rc = _lib.mmls_csv_dims(path.encode(), int(skip_header),
                                ctypes.byref(rows), ctypes.byref(cols))
        if rc != 0:
            raise IOError(f"csv dims failed ({rc}) for {path}")
        out = np.zeros((rows.value, cols.value), np.float64)
        rc = _lib.mmls_csv_parse(
            path.encode(), int(skip_header),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            rows.value, cols.value)
        if rc != 0:
            raise IOError(f"csv parse failed ({rc}) for {path}")
        return out
    return np.loadtxt(path, delimiter=",",
                      skiprows=1 if skip_header else 0, ndmin=2)


def load_libsvm(path: str, num_features: Optional[int] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Parse libsvm lines into dense (x, y)."""
    if ensure_built():
        i64 = ctypes.c_int64
        rows, maxi = i64(), i64()
        rc = _lib.mmls_libsvm_dims(path.encode(), ctypes.byref(rows),
                                   ctypes.byref(maxi))
        if rc != 0:
            raise IOError(f"libsvm dims failed ({rc}) for {path}")
        f = num_features or maxi.value
        x = np.zeros((rows.value, f), np.float64)
        y = np.zeros(rows.value, np.float64)
        rc = _lib.mmls_libsvm_parse(
            path.encode(),
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            y.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            rows.value, f)
        if rc != 0:
            raise IOError(f"libsvm parse failed ({rc}) for {path}")
        return x, y
    xs, ys, maxf = [], [], 0
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            if not parts:
                continue
            ys.append(float(parts[0]))
            row = {}
            for kv in parts[1:]:
                k, v = kv.split(":")
                row[int(k)] = float(v)
                maxf = max(maxf, int(k))
            xs.append(row)
    f = num_features or maxf
    x = np.zeros((len(xs), f), np.float64)
    for i, row in enumerate(xs):
        for k, v in row.items():
            if 1 <= k <= f:
                x[i, k - 1] = v
    return x, np.asarray(ys)


class NativeDataPlane:
    """Facade used by DataFrame readers and BinMapper."""

    is_available = staticmethod(is_available)
    load_csv = staticmethod(load_csv)
    load_libsvm = staticmethod(load_libsvm)
    murmur3_batch = staticmethod(murmur3_batch)
    bin_matrix = staticmethod(bin_matrix)
    level_histogram = staticmethod(level_histogram)
