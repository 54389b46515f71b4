"""Native (C++) runtime bindings via ctypes.

Builds lightgbm_tpu/native/src/*.cpp into libltpu.so on first use — the
framework's native IO layer, standing in for the reference's C++
parser/text-reader stack without a pybind11 dependency.

The cached library is a pure function of its inputs: a sidecar
``<lib>.key`` holds the digest of the source bytes, the compiler flags
and (because the flags include ``-march=native``) this machine's CPU
features.  A library whose key does not match is rebuilt, never loaded
— a tree copied to another machine, or sources edited since the build,
cannot ``dlopen`` someone else's instructions.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

from ..utils.log import Log

_SRC_DIR = os.path.join(os.path.dirname(__file__), "src")
_LIB_PATH = os.path.join(os.path.dirname(__file__), "libltpu.so")
_FLAGS = ("-O3", "-march=native", "-funroll-loops", "-std=c++17",
          "-shared", "-fPIC", "-pthread")
#: element types the table-reading kernels (``ltpu_bin_dense[_mt]``,
#: ``ltpu_bin_cat``) are built for: numpy dtype -> (entry-point suffix,
#: ctypes element type).  A C-contiguous table of one of these dtypes is
#: binned from the buffer it arrived in (native/README.md).
TABLE_DTYPES = {np.dtype(np.float64): ("", ctypes.c_double),
                np.dtype(np.float32): ("_f32", ctypes.c_float)}
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
#: why the library is unavailable in this process (None while it is
#: loaded or not yet asked for); chip_smoke.py prints it
build_error: Optional[str] = None


def _cpu_identity() -> str:
    """What ``-march=native`` resolves against: the CPU's feature
    flags."""
    try:
        with open("/proc/cpuinfo") as f:
            for ln in f:
                if ln.startswith(("flags", "Features")):
                    return ln.split(":", 1)[1].strip()
    except OSError:
        pass
    return platform.machine() + platform.processor()


def build_key(inputs: Sequence[str], flags: Sequence[str]) -> str:
    """Digest of everything a cached shared library depends on: the
    bytes of every input file (sources and headers), the compiler flags
    and the CPU identity."""
    h = hashlib.sha256()
    h.update("\0".join(flags).encode())
    h.update(_cpu_identity().encode())
    for p in sorted(inputs):
        h.update(os.path.basename(p).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build_shared(lib_path: str, srcs: Sequence[str],
                 flags: Sequence[str], deps: Sequence[str] = (),
                 link: Sequence[str] = ()) -> str:
    """``g++ flags srcs -o lib_path link`` unless ``lib_path`` was
    already built from exactly these inputs on this CPU (sidecar
    ``lib_path + ".key"``; ``deps`` are headers that enter the key but
    not the command line).  The library lands by atomic rename BEFORE
    its key is written, so an interrupted build leaves a stale key and
    is redone.  Raises CalledProcessError / FileNotFoundError."""
    key = build_key(list(srcs) + list(deps), list(flags) + list(link))
    key_path = lib_path + ".key"
    try:
        with open(key_path) as f:
            if f.read() == key and os.path.exists(lib_path):
                return lib_path
    except OSError:
        pass
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *flags, *srcs, "-o", tmp, *link],
                       check=True, capture_output=True, text=True)
        os.replace(tmp, lib_path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(key_path + ".tmp", "w") as f:
        f.write(key)
    os.replace(key_path + ".tmp", key_path)
    return lib_path


def _build() -> Optional[str]:
    global build_error
    srcs = [os.path.join(_SRC_DIR, f) for f in sorted(os.listdir(_SRC_DIR))
            if f.endswith(".cpp")]
    try:
        return build_shared(_LIB_PATH, srcs, _FLAGS)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        build_error = f"{e}: {getattr(e, 'stderr', '') or ''}"[-500:]
        Log.warning(f"native build failed ({build_error}); "
                    "falling back to Python IO")
        return None


def _ptr(t):
    return ctypes.POINTER(t)


def get_lib() -> Optional[ctypes.CDLL]:
    # fault seam: every native-lib entry resolves the handle through
    # here, so an injected failure models a broken/unloadable .so at
    # exactly one call site (docs/RELIABILITY.md, seam registry)
    from ..reliability.faults import FAULTS
    FAULTS.fault_point("native.entry")
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        if build_error is not None:
            return None
        path = _build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        c_d, c_l, c_ub = ctypes.c_double, ctypes.c_long, ctypes.c_ubyte
        lib.ltpu_load_csv.restype = _ptr(c_d)
        lib.ltpu_load_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
            _ptr(ctypes.c_int64), _ptr(ctypes.c_int64)]
        lib.ltpu_free.argtypes = [_ptr(c_d)]
        lib.ltpu_count_lines.restype = c_l
        lib.ltpu_count_lines.argtypes = [ctypes.c_char_p]
        lib.ltpu_bin_values.argtypes = [
            _ptr(c_d), ctypes.c_int64, _ptr(c_d), ctypes.c_int32,
            ctypes.c_int32, _ptr(ctypes.c_uint8)]
        # construction-pipeline entry points.  ONE home for every
        # binner signature — dataset.py must not carry its own copies
        # that could drift from the C side.
        for sfx, c_t in TABLE_DTYPES.values():
            dense = getattr(lib, f"ltpu_bin_dense{sfx}")
            dense.restype = None
            dense.argtypes = [
                _ptr(c_t), c_l, c_l, _ptr(c_l), c_l, _ptr(c_d), _ptr(c_l),
                _ptr(c_ub), _ptr(c_l), _ptr(c_ub)]
            dense_mt = getattr(lib, f"ltpu_bin_dense{sfx}_mt")
            dense_mt.restype = None
            dense_mt.argtypes = dense.argtypes + [c_l]
            cat = getattr(lib, f"ltpu_bin_cat{sfx}")
            cat.restype = None
            cat.argtypes = [
                _ptr(c_t), c_l, c_l, c_l, _ptr(ctypes.c_int32), c_l, c_l,
                _ptr(c_ub), c_l]
        lib.ltpu_scatter_cols.restype = None
        lib.ltpu_scatter_cols.argtypes = [
            _ptr(c_ub), c_l, c_l, _ptr(c_l), _ptr(c_ub), c_l]
        lib.ltpu_pack_nibbles.restype = None
        lib.ltpu_pack_nibbles.argtypes = [
            _ptr(c_ub), c_l, c_l, c_l, _ptr(c_ub), c_l]
        lib.ltpu_bin_bundle.restype = None
        lib.ltpu_bin_bundle.argtypes = [
            _ptr(c_ub), c_l, c_l, c_l, _ptr(c_ub), c_l]
        _lib = lib
        return _lib


class text_loader:
    """Namespace used by data_loader.py."""

    @staticmethod
    def load_csv(path: str, sep: str, skip_rows: int) -> np.ndarray:
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        rows = ctypes.c_int64()
        cols = ctypes.c_int64()
        ptr = lib.ltpu_load_csv(path.encode(), sep.encode(), skip_rows,
                                ctypes.byref(rows), ctypes.byref(cols))
        if not ptr:
            raise RuntimeError(f"native parse failed for {path}")
        try:
            n = rows.value * cols.value
            arr = np.ctypeslib.as_array(ptr, shape=(n,)).copy()
        finally:
            lib.ltpu_free(ptr)
        return arr.reshape(rows.value, cols.value)

    @staticmethod
    def count_lines(path: str) -> int:
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        return int(lib.ltpu_count_lines(path.encode()))


def bin_values_native(values: np.ndarray, bounds: np.ndarray,
                      num_bin: int, missing_type: int
                      ) -> Optional[np.ndarray]:
    """Threaded value->bin mapping; None when the native lib is absent."""
    lib = get_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.float64)
    out = np.empty(len(values), dtype=np.uint8)
    lib.ltpu_bin_values(
        values.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        len(values),
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        num_bin, missing_type,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out
