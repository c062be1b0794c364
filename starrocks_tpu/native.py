"""ctypes bridge to the C++ host-runtime kernels (native/sr_native.cpp).

The native library accelerates host-side hot paths the reference implements
in C++ (bucket routing, CSV parse, zonemaps). Build lazily with make on
first use; every entry point has a numpy fallback so the engine works
without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

from . import lockdep

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libsr_native.so")

# module-level build lock: guards the one-shot lazy make + dlopen (_lib /
# _tried are written only inside _load's with-block)
_lock = lockdep.lock("native._lock")
_lib = None
_tried = False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB_PATH):
            try:
                subprocess.run(
                    ["make", "-s"], cwd=_NATIVE_DIR, check=True,
                    capture_output=True, timeout=120,
                )
            except Exception:
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        lib.sr_hash_partition_i64_mt.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ]
        lib.sr_csv_count_rows.restype = ctypes.c_int64
        lib.sr_csv_parse.restype = ctypes.c_int64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def hash_partition_i64(keys: np.ndarray, nbuckets: int) -> np.ndarray:
    """splitmix64 bucket assignment (single int64 key)."""
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    lib = _load()
    out = np.empty(len(keys), dtype=np.int32)
    if lib is None:
        z = keys.view(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
        return (z % np.uint64(nbuckets)).astype(np.int32)
    nthreads = min(os.cpu_count() or 1, 8)
    lib.sr_hash_partition_i64_mt(
        keys.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(keys), nbuckets,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        nthreads,
    )
    return out


# column type tags shared with the C side
CSV_INT64, CSV_FLOAT64, CSV_DATE, CSV_STRING = 0, 1, 2, 3


def parse_csv(data: bytes, types: list, delim: str = ",") :
    """Parse simple (unquoted) CSV into typed numpy columns.

    Returns (columns, null_masks, nrows) or None when the native lib is
    unavailable (caller falls back to pyarrow). String columns come back as
    numpy object arrays (decoded from recorded offsets).
    """
    lib = _load()
    if lib is None:
        return None
    n = lib.sr_csv_count_rows(data, len(data))
    ncols = len(types)
    bufs, ptrs, masks, mask_ptrs = [], [], [], []
    for t in types:
        if t == CSV_STRING:
            b = np.empty(n * 2, dtype=np.int64)
        elif t == CSV_FLOAT64:
            b = np.empty(n, dtype=np.float64)
        else:
            b = np.empty(n, dtype=np.int64)
        bufs.append(b)
        ptrs.append(b.ctypes.data_as(ctypes.c_void_p))
        m = np.empty(n, dtype=np.uint8)
        masks.append(m)
        mask_ptrs.append(m.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)))
    type_arr = (ctypes.c_int32 * ncols)(*types)
    col_arr = (ctypes.c_void_p * ncols)(*[p.value for p in ptrs])
    mask_arr = (ctypes.POINTER(ctypes.c_ubyte) * ncols)(*mask_ptrs)
    got = lib.sr_csv_parse(
        data, len(data), ord(delim), ncols, type_arr, col_arr, mask_arr,
        ctypes.c_int64(n),
    )
    if got < 0:
        return None
    cols = []
    for t, b in zip(types, bufs):
        if t == CSV_STRING:
            offs = b.reshape(n, 2)
            vals = np.array(
                [data[s:e].decode("utf-8", "replace") for s, e in offs[:got]],
                dtype=object,
            )
            cols.append(vals)
        else:
            cols.append(b[:got])
    return cols, [m[:got].astype(bool) for m in masks], int(got)
