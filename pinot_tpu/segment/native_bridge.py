"""ctypes bridge to the native host library (native/pinot_native.cpp).

Builds the shared library on first use with g++ -O3 (cached beside the
source); every entry point degrades to the numpy implementation when the
toolchain or library is unavailable, so the native layer is a pure
accelerator. The reference's equivalent machinery is the hand-unrolled
Java in SURVEY.md §2.9 (FixedBitIntReader etc.).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).resolve().parents[2] / "native" / "pinot_native.cpp"
_SO = _SRC.with_suffix(".so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # build under a per-process name and rename into place: rename is
    # atomic, so concurrent builders (parallel test workers) can never
    # expose a half-written library to each other's CDLL
    tmp = _SO.with_name(f"{_SO.name}.{os.getpid()}.tmp")
    try:
        subprocess.run(
            ["g++", "-O3", "-march=native", "-shared", "-fPIC",
             str(_SRC), "-o", str(tmp)],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        return True
    except (subprocess.SubprocessError, OSError):
        return False
    finally:
        tmp.unlink(missing_ok=True)


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it if needed; None when unavailable.
    Set PINOT_TPU_DISABLE_NATIVE=1 to force the numpy paths."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("PINOT_TPU_DISABLE_NATIVE"):
            return None
        if not _SRC.exists():
            return None
        if not _SO.exists() or _SO.stat().st_mtime < _SRC.stat().st_mtime:
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(str(_SO))
        except OSError:
            return None
        u8 = ctypes.POINTER(ctypes.c_uint8)
        i32 = ctypes.POINTER(ctypes.c_int32)
        i64 = ctypes.POINTER(ctypes.c_int64)
        f64 = ctypes.POINTER(ctypes.c_double)
        u32 = ctypes.POINTER(ctypes.c_uint32)
        lib.unpack_bits.argtypes = [u8, ctypes.c_int, ctypes.c_int64, i32,
                                    ctypes.c_int]
        lib.pack_bits.argtypes = [u32, ctypes.c_int64, ctypes.c_int, u8]
        lib.pack_bitmap.argtypes = [u8, ctypes.c_int64, u8]
        lib.unpack_bitmap.argtypes = [u8, ctypes.c_int64, u8]
        lib.factorize_i64.argtypes = [i64, ctypes.c_int64, i64, i64]
        lib.factorize_i64.restype = ctypes.c_int64
        lib.group_agg_f64.argtypes = [i64, f64, ctypes.c_int64,
                                      ctypes.c_int64, f64, i64, f64, f64]
        for fn in ("lz4_compress", "lz4_decompress",
                   "snappy_compress", "snappy_decompress"):
            f = getattr(lib, fn)
            f.argtypes = [u8, ctypes.c_int64, u8, ctypes.c_int64]
            f.restype = ctypes.c_int64
        _lib = lib
        return _lib


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def unpack_bits(data: np.ndarray, num_bits: int, count: int,
                dtype=np.int32) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None or count == 0:
        return None
    data = np.ascontiguousarray(data, dtype=np.uint8)
    # the fast path reads an 8-byte window at the last value's byte offset
    needed = (count * num_bits + 7) // 8
    padded = 1 if len(data) >= needed + 8 else 0
    out = np.empty(count, dtype=np.int32)
    lib.unpack_bits(_ptr(data, ctypes.c_uint8), num_bits, count,
                    _ptr(out, ctypes.c_int32), padded)
    if dtype == np.int32:
        return out
    if num_bits == 32:
        # full-width values are unsigned in the bitstream: widen without
        # sign extension (matches the numpy path's uint32 view)
        return out.view(np.uint32).astype(dtype)
    return out.astype(dtype)


def pack_bits(values: np.ndarray, num_bits: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.uint32)
    n = len(values)
    out = np.zeros((n * num_bits + 7) // 8, dtype=np.uint8)
    lib.pack_bits(_ptr(values, ctypes.c_uint32), n, num_bits,
                  _ptr(out, ctypes.c_uint8))
    return out


def unpack_bitmap(data: np.ndarray, count: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out = np.empty(count, dtype=np.uint8)
    lib.unpack_bitmap(_ptr(data, ctypes.c_uint8), count,
                      _ptr(out, ctypes.c_uint8))
    return out.view(bool)


def factorize_i64(keys: np.ndarray):
    """(codes, uniques) in first-occurrence order, or None without the lib."""
    lib = get_lib()
    if lib is None:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    n = len(keys)
    codes = np.empty(n, dtype=np.int64)
    uniques = np.empty(n, dtype=np.int64)
    num = lib.factorize_i64(_ptr(keys, ctypes.c_int64), n,
                            _ptr(codes, ctypes.c_int64),
                            _ptr(uniques, ctypes.c_int64))
    return codes, uniques[:num]


def group_agg_f64(codes: np.ndarray, vals: np.ndarray, num_groups: int):
    """(sums, counts, mins, maxs) per group, or None without the lib."""
    lib = get_lib()
    if lib is None:
        return None
    codes = np.ascontiguousarray(codes, dtype=np.int64)
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    sums = np.empty(num_groups, dtype=np.float64)
    counts = np.empty(num_groups, dtype=np.int64)
    mins = np.empty(num_groups, dtype=np.float64)
    maxs = np.empty(num_groups, dtype=np.float64)
    lib.group_agg_f64(_ptr(codes, ctypes.c_int64), _ptr(vals, ctypes.c_double),
                      len(codes), num_groups, _ptr(sums, ctypes.c_double),
                      _ptr(counts, ctypes.c_int64), _ptr(mins, ctypes.c_double),
                      _ptr(maxs, ctypes.c_double))
    return sums, counts, mins, maxs


def _codec_call(fn_name: str, src: bytes, dst_cap: int) -> Optional[bytes]:
    lib = get_lib()
    if lib is None:
        return None
    src_arr = np.frombuffer(src, dtype=np.uint8) if src else np.empty(0, np.uint8)
    src_arr = np.ascontiguousarray(src_arr)
    dst = np.empty(max(1, dst_cap), dtype=np.uint8)
    n = getattr(lib, fn_name)(_ptr(src_arr, ctypes.c_uint8), len(src),
                              _ptr(dst, ctypes.c_uint8), dst_cap)
    if n < 0:
        raise ValueError(f"{fn_name}: corrupt or oversized stream")
    return dst[:n].tobytes()


def lz4_compress(data: bytes) -> Optional[bytes]:
    return _codec_call("lz4_compress", data, len(data) + len(data) // 255 + 16)


def lz4_decompress(blob: bytes, raw_size: int) -> Optional[bytes]:
    return _codec_call("lz4_decompress", blob, raw_size)


def snappy_compress(data: bytes) -> Optional[bytes]:
    return _codec_call("snappy_compress", data, 32 + len(data) + len(data) // 6)


def snappy_decompress(blob: bytes, raw_size: int) -> Optional[bytes]:
    return _codec_call("snappy_decompress", blob, raw_size)
