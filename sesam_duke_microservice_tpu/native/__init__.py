"""ctypes bindings for the C++ host comparators (duke_native.cpp).

Loads ``libduke_native.<hash>.so`` from this directory, compiling it with
g++ on first use (no pybind11 in the image; plain C ABI + ctypes).  The
file name carries a hash of ``duke_native.cpp``, so a library built from
other source (an ignored ``.so`` copied along with a checkout) is never
loaded.  Every entry point degrades gracefully: if the toolchain or
library is unavailable — or ``DUKE_TPU_NATIVE=0`` — ``available()`` is
False and callers (the comparators in core/comparators.py) keep their
pure-Python path, which doubles as the parity oracle
(tests/test_native.py).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

from ..telemetry.env import env_flag

logger = logging.getLogger("duke-tpu-native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "duke_native.cpp")


def _lib_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libduke_native.{digest}.so")


_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(lib: str) -> bool:
    # compile to a private temp name, then rename: os.rename is atomic on
    # POSIX, so a concurrent process never dlopens a half-written library
    tmp = lib + f".tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.rename(tmp, lib)
        for stale in glob.glob(os.path.join(_HERE, "libduke_native*.so")):
            if stale != lib:
                os.unlink(stale)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logger.warning("native comparator build failed (%s); using pure Python", e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not env_flag("DUKE_TPU_NATIVE", True):
            return None
        path = _lib_path()
        if not os.path.exists(path) and not _build(path):
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            logger.warning("could not load %s (%s); using pure Python", path, e)
            return None
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f64p = ctypes.POINTER(ctypes.c_double)
        lib.duke_lev_sim_batch.argtypes = [u32p, i64p, u32p, i64p,
                                           ctypes.c_int64, f64p]
        lib.duke_lev_sim_batch.restype = None
        lib.duke_jaro_winkler_batch.argtypes = [
            u32p, i64p, u32p, i64p, ctypes.c_int64,
            ctypes.c_double, ctypes.c_double, ctypes.c_int64, f64p]
        lib.duke_jaro_winkler_batch.restype = None
        lib.duke_weighted_lev_batch.argtypes = [
            u32p, i64p, u32p, i64p, ctypes.c_int64,
            ctypes.c_double, ctypes.c_double, ctypes.c_double, f64p]
        lib.duke_weighted_lev_batch.restype = None
        lib.duke_lev_distance.argtypes = [u32p, ctypes.c_int64, u32p,
                                          ctypes.c_int64]
        lib.duke_lev_distance.restype = ctypes.c_int64
        lib.duke_embed_batch.argtypes = [
            u32p, i64p, ctypes.POINTER(ctypes.c_uint64), i64p,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float)]
        lib.duke_embed_batch.restype = None
        lib.duke_fnv1a64_batch.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), i64p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.duke_fnv1a64_batch.restype = None
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.duke_gram_set_batch.argtypes = [
            u32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            i32p, i32p]
        lib.duke_gram_set_batch.restype = None
        # scalar entry points take the UTF-32 bytes object directly
        # (c_char_p), skipping numpy packing
        cc = ctypes.c_char_p
        i64 = ctypes.c_int64
        dbl = ctypes.c_double
        lib.duke_lev_sim.argtypes = [cc, i64, cc, i64]
        lib.duke_lev_sim.restype = dbl
        lib.duke_jaro_winkler.argtypes = [cc, i64, cc, i64, dbl, dbl, i64]
        lib.duke_jaro_winkler.restype = dbl
        lib.duke_weighted_lev.argtypes = [cc, i64, cc, i64, dbl, dbl, dbl]
        lib.duke_weighted_lev.restype = dbl
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


_U32P = ctypes.POINTER(ctypes.c_uint32)
_I64P = ctypes.POINTER(ctypes.c_int64)
_F64P = ctypes.POINTER(ctypes.c_double)


def _pack(strings: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
    """UTF-32 codepoint buffer + int64 offsets (len n+1)."""
    offsets = np.zeros(len(strings) + 1, dtype=np.int64)
    chunks = []
    total = 0
    for i, s in enumerate(strings):
        chunk = s.encode("utf-32-le", "surrogatepass")
        chunks.append(chunk)
        total += len(chunk) // 4
        offsets[i + 1] = total
    if total:
        buf = np.frombuffer(b"".join(chunks), dtype="<u4")
    else:
        buf = np.zeros(1, dtype=np.uint32)  # valid pointer for empty input
    return buf, offsets


def _ptrs(buf: np.ndarray, off: np.ndarray):
    return buf.ctypes.data_as(_U32P), off.ctypes.data_as(_I64P)


def fnv1a64_bytes_batch(bufs: Sequence[bytes]) -> np.ndarray:
    """Bulk FNV-1a64 over pre-encoded UTF-8 buffers -> (N,) uint64.

    Bit-identical to ``ops.features.fnv1a64`` (which hashes the UTF-8
    encoding); the ingest path hashes every value + q-gram + token per
    record, so this one C pass replaces the numpy grouped-fold hot spot.
    """
    lib = _load()
    assert lib is not None
    n = len(bufs)
    off = np.zeros(n + 1, dtype=np.int64)
    total = 0
    for i, b in enumerate(bufs):
        total += len(b)
        off[i + 1] = total
    buf = (np.frombuffer(b"".join(bufs), dtype=np.uint8) if total
           else np.zeros(1, dtype=np.uint8))
    out = np.empty((n,), dtype=np.uint64)
    lib.duke_fnv1a64_batch(
        buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        off.ctypes.data_as(_I64P), n,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
    )
    return out


def gram_set_batch(values: Sequence[str], q: int,
                   max_grams: int, set_pad: int) -> Tuple[np.ndarray, np.ndarray]:
    """Bulk q-gram set ids: ((N, max_grams) int32 sorted-distinct folded
    gram hashes padded with ``set_pad``, (N,) int32 counts).  Bit-identical
    to the Python path in ops.features (qgrams + hash + sorted(set))."""
    lib = _load()
    assert lib is not None
    buf, off = _pack(values)
    n = len(values)
    grams = np.full((n, max_grams), set_pad, dtype=np.int32)
    counts = np.zeros((n,), dtype=np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.duke_gram_set_batch(
        *_ptrs(buf, off), n, q, max_grams,
        grams.ctypes.data_as(i32p), counts.ctypes.data_as(i32p),
    )
    return grams, counts


def lev_sim_batch(a: Sequence[str], b: Sequence[str]) -> np.ndarray:
    lib = _load()
    assert lib is not None and len(a) == len(b)
    abuf, aoff = _pack(a)
    bbuf, boff = _pack(b)
    out = np.empty(len(a), dtype=np.float64)
    lib.duke_lev_sim_batch(*_ptrs(abuf, aoff), *_ptrs(bbuf, boff),
                           len(a), out.ctypes.data_as(_F64P))
    return out


def jaro_winkler_batch(a: Sequence[str], b: Sequence[str], *,
                       prefix_scale: float = 0.1,
                       boost_threshold: float = 0.7,
                       max_prefix: int = 4) -> np.ndarray:
    lib = _load()
    assert lib is not None and len(a) == len(b)
    abuf, aoff = _pack(a)
    bbuf, boff = _pack(b)
    out = np.empty(len(a), dtype=np.float64)
    lib.duke_jaro_winkler_batch(*_ptrs(abuf, aoff), *_ptrs(bbuf, boff),
                                len(a), prefix_scale, boost_threshold,
                                max_prefix, out.ctypes.data_as(_F64P))
    return out


def weighted_lev_batch(a: Sequence[str], b: Sequence[str], *,
                       digit_weight: float = 2.0, letter_weight: float = 1.0,
                       other_weight: float = 1.0) -> np.ndarray:
    lib = _load()
    assert lib is not None and len(a) == len(b)
    abuf, aoff = _pack(a)
    bbuf, boff = _pack(b)
    out = np.empty(len(a), dtype=np.float64)
    lib.duke_weighted_lev_batch(*_ptrs(abuf, aoff), *_ptrs(bbuf, boff),
                                len(a), digit_weight, letter_weight,
                                other_weight, out.ctypes.data_as(_F64P))
    return out


def embed_batch(value_strings: Sequence[str], salts: np.ndarray,
                rec_off: np.ndarray, dim: int) -> np.ndarray:
    """Hashed-n-gram record embeddings (ops.encoder parity, bulk).

    ``value_strings`` are the already padded+lowercased per-value strings
    (concatenated across records), ``salts`` the per-value uint64 property
    salts, ``rec_off`` the int64 record->value-range offsets (n_rec+1).
    Returns (n_rec, dim) float32, rows L2-normalized.
    """
    lib = _load()
    assert lib is not None
    buf, off = _pack(value_strings)
    salts = np.ascontiguousarray(salts, dtype=np.uint64)
    rec_off = np.ascontiguousarray(rec_off, dtype=np.int64)
    n_rec = len(rec_off) - 1
    out = np.zeros((n_rec, dim), dtype=np.float32)
    lib.duke_embed_batch(
        *_ptrs(buf, off),
        salts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        rec_off.ctypes.data_as(_I64P), n_rec, dim,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
    )
    return out


def lev_sim(a: str, b: str) -> float:
    lib = _load()
    return lib.duke_lev_sim(a.encode("utf-32-le", "surrogatepass"), len(a),
                            b.encode("utf-32-le", "surrogatepass"), len(b))


def jaro_winkler(a: str, b: str, prefix_scale: float = 0.1,
                 boost_threshold: float = 0.7, max_prefix: int = 4) -> float:
    lib = _load()
    return lib.duke_jaro_winkler(a.encode("utf-32-le", "surrogatepass"), len(a),
                                 b.encode("utf-32-le", "surrogatepass"), len(b),
                                 prefix_scale, boost_threshold, max_prefix)


def weighted_lev(a: str, b: str, digit_weight: float = 2.0,
                 letter_weight: float = 1.0,
                 other_weight: float = 1.0) -> float:
    lib = _load()
    return lib.duke_weighted_lev(a.encode("utf-32-le", "surrogatepass"), len(a),
                                 b.encode("utf-32-le", "surrogatepass"), len(b),
                                 digit_weight, letter_weight, other_weight)


def lev_distance(a: str, b: str) -> int:
    lib = _load()
    assert lib is not None
    abuf = np.frombuffer(a.encode("utf-32-le", "surrogatepass"), dtype="<u4") if a else np.zeros(1, dtype=np.uint32)
    bbuf = np.frombuffer(b.encode("utf-32-le", "surrogatepass"), dtype="<u4") if b else np.zeros(1, dtype=np.uint32)
    return int(lib.duke_lev_distance(
        abuf.ctypes.data_as(_U32P), len(a), bbuf.ctypes.data_as(_U32P), len(b)))
