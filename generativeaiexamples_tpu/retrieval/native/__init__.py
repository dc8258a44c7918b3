"""ctypes loader for the native top-k kernels, compiled on demand.

First call compiles ``topk.cpp`` with g++ (OpenMP) into a shared library
next to this file, named by the SOURCE'S CONTENT HASH — so a binary built
from other source (a stale one, or one copied in with the tree) is never
loaded, whatever its mtime says. If no toolchain is available the callers
fall back to numpy, logged once at WARNING. This is the framework's own
native-code answer
to the reference's FAISS / knowhere C++ search engines
(reference: common/utils.py:181-198).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "topk.cpp")
with open(_SRC, "rb") as _f:
    _LIB = os.path.join(
        _HERE, f"libgaietopk-{hashlib.sha256(_f.read()).hexdigest()[:12]}.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_i64 = ctypes.c_int64
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def _compile() -> bool:
    # Build under a private name, then rename into place: a concurrent
    # process never loads a half-written library.
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fopenmp", "-shared", "-fPIC", "-std=c++17",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        logger.warning(
            "native topk unavailable (%s); using the numpy fallback", exc)
        return False


def load() -> Optional[ctypes.CDLL]:
    """The library, compiling it on first use; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB) and not _compile():
            return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError:
            # Right source, foreign architecture (copied between hosts):
            # rebuild once before giving up.
            if not _compile():
                return None
            try:
                lib = ctypes.CDLL(_LIB)
            except OSError as exc:
                logger.warning("native topk load failed (%s); using the "
                               "numpy fallback", exc)
                return None
        lib.gaie_brute_topk.argtypes = [
            _f32p, ctypes.c_void_p, ctypes.c_void_p, _i64, _i64,
            _f32p, _i64, _i64, ctypes.c_int, _i64p, _f32p]
        lib.gaie_ivf_search.argtypes = [
            _f32p, ctypes.c_void_p, ctypes.c_void_p, _i64,
            _f32p, _i64, _i64p, _i64p,
            _f32p, _i64, _i64, _i64, ctypes.c_int, _i64p, _f32p]
        lib.gaie_num_threads.restype = ctypes.c_int
        _lib = lib
        return _lib


def _opt(arr: Optional[np.ndarray]) -> Optional[ctypes.c_void_p]:
    if arr is None:
        return None
    return arr.ctypes.data_as(ctypes.c_void_p)


def brute_topk(base: np.ndarray, queries: np.ndarray, k: int, metric: int,
               base_sq: Optional[np.ndarray] = None,
               live: Optional[np.ndarray] = None,
               ) -> Optional[tuple[np.ndarray, np.ndarray]]:
    """(idx, score) each (Q, k), or None when the native lib is unavailable."""
    lib = load()
    if lib is None:
        return None
    nq, n = queries.shape[0], base.shape[0]
    idx = np.empty((nq, k), np.int64)
    score = np.empty((nq, k), np.float32)
    lib.gaie_brute_topk(base, _opt(base_sq), _opt(live), n, base.shape[1],
                        queries, nq, k, metric, idx, score)
    return idx, score


def ivf_search(base: np.ndarray, centroids: np.ndarray, offsets: np.ndarray,
               items: np.ndarray, queries: np.ndarray, k: int, nprobe: int,
               metric: int, base_sq: Optional[np.ndarray] = None,
               live: Optional[np.ndarray] = None,
               ) -> Optional[tuple[np.ndarray, np.ndarray]]:
    lib = load()
    if lib is None:
        return None
    nq = queries.shape[0]
    idx = np.empty((nq, k), np.int64)
    score = np.empty((nq, k), np.float32)
    lib.gaie_ivf_search(base, _opt(base_sq), _opt(live), base.shape[1],
                        centroids, centroids.shape[0], offsets, items,
                        queries, nq, k, nprobe, metric, idx, score)
    return idx, score
