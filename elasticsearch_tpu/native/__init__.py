"""ctypes bindings for the C++ hot-loop kernels in native/es_native.cc.

The TPU owns vector scoring (ops/, parallel/); these cover the host-side
scalar loops the reference delegates to Lucene's Java hot loops
(SURVEY.md §2.9): sorted-postings intersection, union-with-score-sum,
fused BM25, and top-k selection.

The library is compiled on first use with `make` (g++ is in the image;
pybind11 is not, hence the plain C ABI + ctypes). Every binding has a
numpy fallback, so the package works — just slower — without a compiler.
Callers use the module-level functions and never need to know which
implementation ran; `AVAILABLE` reports it for stats/tests.
"""

from __future__ import annotations

import ctypes
import fcntl
import logging
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger("elasticsearch_tpu.native")

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libes_native.so")

_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None
AVAILABLE = False


def _up_to_date(target: str, src: str) -> bool:
    return (os.path.exists(target)
            and os.path.getmtime(target) >= os.path.getmtime(src))


def build_target(target: str, src: str, timeout: int = 180) -> None:
    """`make` one native target, safe under concurrent first use.

    A fresh checkout has no build outputs (they are git-ignored), so every
    process that starts together — pytest workers, a server beside its
    client — reaches this at once. The Makefile links to a temporary name
    and renames it into place, so a target that exists is always whole;
    the file lock only keeps the others from compiling the same thing
    again. Raises RuntimeError carrying the compiler's output."""
    path = os.path.join(_NATIVE_DIR, target)
    src_path = os.path.join(_NATIVE_DIR, src)
    if not os.path.exists(src_path):
        if os.path.exists(path):
            return
        raise RuntimeError(f"native source {src_path} is missing")
    if _up_to_date(path, src_path):
        return
    with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _up_to_date(path, src_path):
            return
        try:
            proc = subprocess.run(["make", "-C", _NATIVE_DIR, target],
                                  capture_output=True, text=True,
                                  timeout=timeout)
        except (OSError, subprocess.TimeoutExpired) as exc:
            raise RuntimeError(f"make {target} did not run: {exc}") from exc
        if proc.returncode != 0 or not os.path.exists(path):
            raise RuntimeError(
                f"make {target} failed (rc {proc.returncode}):\n"
                f"{proc.stdout}{proc.stderr}")


def _load() -> Optional[ctypes.CDLL]:
    """The library, or None when it could not be built or loaded. The
    reason is kept in `_load_error` (and logged once): bindings with a
    numpy fallback go on without it, `require()` raises it."""
    global _lib, AVAILABLE, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        return None  # build/load failed once; don't rebuild per call
    try:
        build_target("libes_native.so", "es_native.cc")
        lib = ctypes.CDLL(_SO_PATH)
    except (RuntimeError, OSError) as exc:
        _load_error = str(exc)
        logger.warning("native library unavailable, numpy fallbacks "
                       "serve: %s", _load_error)
        return None
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.es_bm25_score.argtypes = [i32p, f32p, ctypes.c_int64,
                                  ctypes.c_float, ctypes.c_float,
                                  ctypes.c_float, ctypes.c_float,
                                  ctypes.c_float, f32p]
    lib.es_bm25_score.restype = None
    lib.es_intersect_i64.argtypes = [i64p, ctypes.c_int64, i64p,
                                     ctypes.c_int64, i64p, i64p]
    lib.es_intersect_i64.restype = ctypes.c_int64
    lib.es_union_sum_i64.argtypes = [i64p, f32p, ctypes.c_int64,
                                     i64p, f32p, ctypes.c_int64, i64p, f32p]
    lib.es_union_sum_i64.restype = ctypes.c_int64
    lib.es_topk_f32.argtypes = [f32p, ctypes.c_int64, ctypes.c_int64, i32p]
    lib.es_topk_f32.restype = ctypes.c_int64
    _lib = lib
    AVAILABLE = True
    return lib


def require() -> ctypes.CDLL:
    """The library, for callers with no fallback: raises with the build
    or load error (the compiler's output) instead of returning None."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native kernels unavailable: {_load_error}")
    return lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def bm25_score(freqs: np.ndarray, lengths: np.ndarray, idf: float,
               avg_len: float, k1: float, b: float,
               boost: float) -> np.ndarray:
    """Fused BM25 term scores for one posting list."""
    freqs = np.ascontiguousarray(freqs, dtype=np.int32)
    lengths = np.ascontiguousarray(lengths, dtype=np.float32)
    lib = _load()
    if lib is None:
        f = freqs.astype(np.float32)
        tf = f / (f + k1 * (1.0 - b + (b / avg_len if avg_len else 0.0) * lengths))
        return (boost * idf * (k1 + 1.0) * tf).astype(np.float32)
    out = np.empty(len(freqs), dtype=np.float32)
    lib.es_bm25_score(_ptr(freqs, ctypes.c_int32),
                      _ptr(lengths, ctypes.c_float), len(freqs),
                      idf, avg_len, k1, b, boost,
                      _ptr(out, ctypes.c_float))
    return out


def intersect_sorted(a: np.ndarray, b: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Positions (ia, ib) where two sorted unique int64 arrays meet —
    the np.intersect1d(..., return_indices=True) contract."""
    a = np.ascontiguousarray(a, dtype=np.int64)
    b = np.ascontiguousarray(b, dtype=np.int64)
    lib = _load()
    if lib is None:
        _, ia, ib = np.intersect1d(a, b, assume_unique=True,
                                   return_indices=True)
        return ia, ib
    cap = min(len(a), len(b))
    ia = np.empty(cap, dtype=np.int64)
    ib = np.empty(cap, dtype=np.int64)
    n = lib.es_intersect_i64(_ptr(a, ctypes.c_int64), len(a),
                             _ptr(b, ctypes.c_int64), len(b),
                             _ptr(ia, ctypes.c_int64),
                             _ptr(ib, ctypes.c_int64))
    return ia[:n], ib[:n]


def union_sum(a: np.ndarray, sa: Optional[np.ndarray],
              b: np.ndarray, sb: Optional[np.ndarray]
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Union of sorted unique int64 row arrays, summing aligned scores on
    rows present in both (bool-SHOULD accumulation)."""
    a = np.ascontiguousarray(a, dtype=np.int64)
    b = np.ascontiguousarray(b, dtype=np.int64)
    if sa is not None:
        sa = np.ascontiguousarray(sa, dtype=np.float32)
    if sb is not None:
        sb = np.ascontiguousarray(sb, dtype=np.float32)
    lib = _load()
    if lib is None:
        rows = np.union1d(a, b)
        scores = np.zeros(len(rows), dtype=np.float32)
        if sa is not None and len(a):
            scores[np.searchsorted(rows, a)] += sa
        if sb is not None and len(b):
            scores[np.searchsorted(rows, b)] += sb
        return rows, scores
    cap = len(a) + len(b)
    rows = np.empty(cap, dtype=np.int64)
    scores = np.empty(cap, dtype=np.float32)
    null_f32 = ctypes.POINTER(ctypes.c_float)()
    n = lib.es_union_sum_i64(
        _ptr(a, ctypes.c_int64),
        _ptr(sa, ctypes.c_float) if sa is not None else null_f32, len(a),
        _ptr(b, ctypes.c_int64),
        _ptr(sb, ctypes.c_float) if sb is not None else null_f32, len(b),
        _ptr(rows, ctypes.c_int64), _ptr(scores, ctypes.c_float))
    return rows[:n], scores[:n]


def topk(scores: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k best scores ordered by (score desc, index asc) —
    the tie-break `SearchPhaseController.mergeTopDocs` uses."""
    scores = np.ascontiguousarray(scores, dtype=np.float32)
    lib = _load()
    if lib is None:
        # full (score desc, index asc) sort: argpartition would leave the
        # boundary cut nondeterministic on ties, diverging from the native
        # heap's ordering — a no-compiler host pays O(n log n) instead
        order = np.lexsort((np.arange(len(scores)), -scores))
        return order[:k].astype(np.int32)
    k = min(k, len(scores))
    out = np.empty(max(k, 0), dtype=np.int32)
    n = lib.es_topk_f32(_ptr(scores, ctypes.c_float), len(scores), k,
                        _ptr(out, ctypes.c_int32))
    return out[:n]


# Build/load at import so the first search request never pays the compile
# (a stat-only no-op once libes_native.so is newer than the source).
_load()
