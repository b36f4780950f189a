"""ctypes bindings for the C++ host codec (rs_codec.cpp, shipped
inside this package so installed copies keep the native fast path).

The library is built lazily with g++ on first use and cached next to the
source under a name keyed on the source's SHA-256, so only a build of the
``rs_codec.cpp`` beside it can ever load (a stale build of other source
bytes carries another name and is ignored); every entry point degrades to the NumPy oracle when the toolchain
or the .so is unavailable, so the framework never *requires* the native
path — it is the fast host data plane, not a correctness dependency.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_SRC = Path(__file__).resolve().parent / "rs_codec.cpp"
_LIB = _SRC.with_name(
    f"rs_codec.{hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]}.so"
)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    # Compile to a unique temp path and rename into place: rename is atomic
    # on POSIX, so a concurrent builder (parallel test processes) or a
    # killed build can never leave a truncated .so that a later process
    # would CDLL.
    tmp = _LIB.with_suffix(f".tmp{os.getpid()}.so")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp), str(_SRC)],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def load() -> Optional[ctypes.CDLL]:
    """The codec library, building it if needed; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not _LIB.exists() and not _build():
            return None
        try:
            lib = ctypes.CDLL(str(_LIB))
        except OSError:
            return None
        lib.rs_apply_matrix.argtypes = [
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_long,
        ]
        lib.rs_apply_matrix.restype = None
        lib.rs_gf_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
        lib.rs_gf_mul.restype = ctypes.c_uint8
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def apply_matrix(matrix: np.ndarray, rows: np.ndarray) -> Optional[np.ndarray]:
    """out[r] = XOR_c mul(matrix[r, c], rows[c]) via the C++ codec.

    ``rows``: u8[in_rows, ...] (trailing dims flattened); returns
    u8[out_rows, ...] or None when the library is unavailable.
    """
    lib = load()
    if lib is None:
        return None
    matrix = np.ascontiguousarray(matrix, np.uint8)
    rows_c = np.ascontiguousarray(rows, np.uint8)
    out_rows, in_rows = matrix.shape
    assert rows_c.shape[0] == in_rows
    row_bytes = int(rows_c[0].size)
    out = np.empty((out_rows,) + rows_c.shape[1:], np.uint8)
    lib.rs_apply_matrix(
        rows_c.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        matrix.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        in_rows,
        out_rows,
        row_bytes,
    )
    return out


def gf_mul(a: int, b: int) -> Optional[int]:
    lib = load()
    if lib is None:
        return None
    return int(lib.rs_gf_mul(a, b))
