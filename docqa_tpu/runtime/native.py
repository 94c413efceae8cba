"""ctypes bindings for the native host library (``native/docqa_native.cpp``).

The reference reached its native host components through SWIG/pickle
(FAISS serialization, ``semantic-indexer/indexer.py:26-30``); here the
snapshot codec is in-repo C++ behind a minimal ctypes surface, with a pure
NumPy fallback so nothing hard-depends on the toolchain at runtime.

API:
  lib = load(build_if_missing=True)   → _NativeLib or None
  write_shard(path, arr)              — checksummed DNS1 shard (f32 or bf16)
  read_shard(path, verify_crc=True)   → np.ndarray [count, dim]
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

from docqa_tpu.runtime.metrics import get_logger

log = get_logger("docqa.native")

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_DTYPE_F32, _DTYPE_BF16 = 0, 1
_ERRORS = {
    -1: "io error",
    -2: "bad header",
    -3: "size mismatch",
    -4: "crc mismatch",
    -5: "bad arguments",
}

_lock = threading.Lock()
_cached: Optional["_NativeLib"] = None
_load_failed = False


class ShardError(RuntimeError):
    pass


class _NativeLib:
    def __init__(self, path: str) -> None:
        lib = ctypes.CDLL(path)
        lib.dn_crc32.restype = ctypes.c_uint32
        lib.dn_crc32.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.dn_shard_write.restype = ctypes.c_int
        lib.dn_shard_write.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_uint32,
            ctypes.c_uint32,
        ]
        lib.dn_shard_info.restype = ctypes.c_int
        lib.dn_shard_info.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.dn_shard_read.restype = ctypes.c_int
        lib.dn_shard_read.argtypes = [
            ctypes.c_char_p,
            ctypes.c_void_p,
            ctypes.c_uint64,
            ctypes.c_int,
        ]
        lib.dn_f32_to_bf16.restype = None
        lib.dn_f32_to_bf16.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        lib.dn_bf16_to_f32.restype = None
        lib.dn_bf16_to_f32.argtypes = [
            ctypes.c_void_p,
            ctypes.c_void_p,
            ctypes.c_size_t,
        ]
        self._lib = lib

    # ---- shard codec ---------------------------------------------------------

    def write_shard(self, path: str, arr: np.ndarray, bf16: bool = False) -> None:
        arr = np.ascontiguousarray(arr, np.float32)
        if arr.ndim != 2:
            raise ValueError("expected [count, dim] array")
        count, dim = arr.shape
        if bf16:
            out = np.empty(arr.size, np.uint16)
            self._lib.dn_f32_to_bf16(
                arr.ctypes.data_as(ctypes.c_void_p),
                out.ctypes.data_as(ctypes.c_void_p),
                arr.size,
            )
            data, dtype = out, _DTYPE_BF16
        else:
            data, dtype = arr, _DTYPE_F32
        rc = self._lib.dn_shard_write(
            path.encode(),
            data.ctypes.data_as(ctypes.c_void_p),
            count,
            dim,
            dtype,
        )
        if rc != 0:
            raise ShardError(f"shard write failed: {_ERRORS.get(rc, rc)}")

    def read_shard(self, path: str, verify_crc: bool = True) -> np.ndarray:
        dtype = ctypes.c_uint32()
        dim = ctypes.c_uint32()
        count = ctypes.c_uint64()
        nbytes = ctypes.c_uint64()
        rc = self._lib.dn_shard_info(
            path.encode(),
            ctypes.byref(dtype),
            ctypes.byref(dim),
            ctypes.byref(count),
            ctypes.byref(nbytes),
        )
        if rc != 0:
            raise ShardError(f"shard info failed: {_ERRORS.get(rc, rc)}")
        raw = np.empty(
            nbytes.value // (2 if dtype.value == _DTYPE_BF16 else 4),
            np.uint16 if dtype.value == _DTYPE_BF16 else np.float32,
        )
        rc = self._lib.dn_shard_read(
            path.encode(),
            raw.ctypes.data_as(ctypes.c_void_p),
            nbytes.value,
            1 if verify_crc else 0,
        )
        if rc != 0:
            raise ShardError(f"shard read failed: {_ERRORS.get(rc, rc)}")
        if dtype.value == _DTYPE_BF16:
            out = np.empty(raw.size, np.float32)
            self._lib.dn_bf16_to_f32(
                raw.ctypes.data_as(ctypes.c_void_p),
                out.ctypes.data_as(ctypes.c_void_p),
                raw.size,
            )
        else:
            out = raw
        return out.reshape(count.value, dim.value)

    def crc32(self, data: bytes) -> int:
        buf = (ctypes.c_char * len(data)).from_buffer_copy(data)
        return int(self._lib.dn_crc32(ctypes.cast(buf, ctypes.c_void_p), len(data)))


def load(build_if_missing: bool = True) -> Optional[_NativeLib]:
    """Load (building on demand) the native library; None if unavailable.

    The library's file name is keyed on the source and compile flags
    (``native/build.py``), so only a build of THIS checkout's source is
    ever loaded — a stray ``.so`` from another tree or another machine's
    ``-march`` is not the file looked for."""
    global _cached, _load_failed
    with _lock:
        if _cached is not None:
            return _cached
        if _load_failed:
            return None
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "docqa_native_build",
                os.path.join(_REPO_ROOT, "native", "build.py"),
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            path = mod.build() if build_if_missing else mod.lib_path()
        except Exception:
            log.exception("native build failed; using NumPy fallback")
            _load_failed = True
            return None
        if not os.path.exists(path):
            _load_failed = True
            return None
        try:
            _cached = _NativeLib(path)
        except OSError:
            log.exception("native load failed; using NumPy fallback")
            _load_failed = True
            return None
        return _cached


# ---------------------------------------------------------------------------
# Pure-Python DNS1 codec (same format, no toolchain needed) — guarantees a
# snapshot written on a host WITH g++ restores on a host WITHOUT one.
# ---------------------------------------------------------------------------

import struct
import zlib

_HEADER = struct.Struct("<4sIIIQQI28x")  # magic, hsize, dtype, dim, count, bytes, crc
assert _HEADER.size == 64


def _py_write_shard(path: str, arr: np.ndarray, bf16: bool = False) -> None:
    arr = np.ascontiguousarray(arr, np.float32)
    if arr.ndim != 2:
        raise ValueError("expected [count, dim] array")
    count, dim = arr.shape
    if bf16:
        import ml_dtypes  # ships with jax; same round-to-nearest-even

        payload = arr.astype(ml_dtypes.bfloat16).view(np.uint16).tobytes()
        dtype = _DTYPE_BF16
    else:
        payload = arr.tobytes()
        dtype = _DTYPE_F32
    header = _HEADER.pack(
        b"DNS1", 64, dtype, dim, count, len(payload),
        zlib.crc32(payload) & 0xFFFFFFFF,
    )
    with open(path, "wb") as f:
        f.write(header)
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())


def _py_read_shard(path: str, verify_crc: bool = True) -> np.ndarray:
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < 64:
        raise ShardError("bad header")
    magic, hsize, dtype, dim, count, nbytes, crc = _HEADER.unpack_from(raw)
    if magic != b"DNS1" or hsize != 64 or dtype > 1 or dim == 0:
        raise ShardError("bad header")
    payload = raw[64:]
    if len(payload) != nbytes or nbytes != count * dim * (2 if dtype else 4):
        raise ShardError("size mismatch")
    if verify_crc and (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
        raise ShardError("crc mismatch")
    if dtype == _DTYPE_BF16:
        import ml_dtypes

        arr = np.frombuffer(payload, np.uint16).view(ml_dtypes.bfloat16)
        return np.asarray(arr, np.float32).reshape(count, dim)
    return np.frombuffer(payload, np.float32).reshape(count, dim).copy()


# ---------------------------------------------------------------------------
# codec front door: one on-disk format, native fast path when available
# ---------------------------------------------------------------------------

def write_vectors(path: str, arr: np.ndarray, bf16: bool = False) -> str:
    """Write vectors as a checksummed DNS1 shard; returns the path written."""
    p = path + ".dns"
    lib = load()
    if lib is not None:
        lib.write_shard(p, arr, bf16=bf16)
    else:
        _py_write_shard(p, arr, bf16=bf16)
    return p


def read_vectors(path: str) -> np.ndarray:
    if path.endswith(".dns"):
        lib = load()
        if lib is not None:
            return lib.read_shard(path)
        return _py_read_shard(path)
    return np.load(path)  # legacy .npy snapshots
