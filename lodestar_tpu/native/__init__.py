"""Native (C++) host components, loaded via ctypes.

The reference leans on native packages for its host hot loops
(@chainsafe/as-sha256 WASM, leveldown C++ — SURVEY §2b); this package is
the tpu-framework equivalent: small C++ kernels compiled on first use
with the baked-in toolchain and bound through ctypes (no pybind11 in the
image). Everything degrades gracefully — if the toolchain or the build
is unavailable, consumers fall back to the pure-Python paths.

Current components:
* sha256_batch — batched pair-hashing for sub-device merkle levels
  (SHA-NI when the CPU has it, portable scalar otherwise, threaded for
  large batches). Consumed by `lodestar_tpu.ssz.hash.hash_nodes_cpu`.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

__all__ = [
    "build_shared_lib",
    "sha256_available",
    "sha256_backend",
    "hash_pairs",
    "load_sha256",
]

_DIR = os.path.dirname(os.path.abspath(__file__))
_CXX = ["g++", "-O3", "-std=c++17", "-fPIC", "-shared", "-pthread"]

_lock = threading.Lock()
_lib = None
_load_failed = False


def build_shared_lib(stem: str, sources: list[str], timeout_s: float) -> str | None:
    """Path of the shared library built from `sources` (`sources[0]` is
    compiled, the rest are headers it includes), compiling it when no
    binary of this exact source content and command exists. The content
    hash is part of the file name, so a copied or freshly checked-out
    tree never loads a binary its sources did not produce, whatever the
    mtimes say. None when the toolchain is missing or the build fails
    (consumers fall back to the pure-Python paths and report it)."""
    try:
        h = hashlib.sha256(" ".join(_CXX).encode())
        for name in sources:
            with open(os.path.join(_DIR, name), "rb") as f:
                h.update(f.read())
        so = os.path.join(_DIR, f"{stem}-{h.hexdigest()[:16]}.so")
        if os.path.exists(so):
            return so
        # pid-unique temp target: concurrent builders (multiple node
        # processes, pytest-xdist) must not publish each other's
        # half-written output through the shared rename
        tmp = f"{so[:-3]}.{os.getpid()}.so.tmp"
        try:
            res = subprocess.run(
                [*_CXX, os.path.join(_DIR, sources[0]), "-o", tmp],
                capture_output=True,
                timeout=timeout_s,
            )
            if res.returncode != 0:
                return None
            os.replace(tmp, so)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        for stale in glob.glob(os.path.join(_DIR, f"{stem}*.so")):
            if stale != so:
                os.unlink(stale)
        return so
    except (OSError, subprocess.SubprocessError):
        return None


def load_sha256():
    """The loaded ctypes lib, or None if build/load failed (cached)."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        so = build_shared_lib("libsha256batch", ["sha256_batch.cpp"], 120)
        if so is None:
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(so)
            lib.sha256_pairs.argtypes = [
                ctypes.POINTER(ctypes.c_uint8),
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.sha256_pairs.restype = None
            lib.sha256_backend.argtypes = []
            lib.sha256_backend.restype = ctypes.c_int
            _lib = lib
        except OSError:
            _load_failed = True
            return None
    return _lib


def sha256_available() -> bool:
    return load_sha256() is not None


def sha256_backend() -> str:
    """'shani' | 'scalar' | 'unavailable'."""
    lib = load_sha256()
    if lib is None:
        return "unavailable"
    return "shani" if lib.sha256_backend() == 1 else "scalar"


def hash_pairs(data: np.ndarray) -> np.ndarray:
    """SHA-256 of adjacent 32-byte node pairs. data: (2N, 32) uint8 ->
    (N, 32) uint8. Caller must have checked sha256_available()."""
    lib = load_sha256()
    n = data.shape[0] // 2
    src = np.ascontiguousarray(data[: 2 * n], dtype=np.uint8)
    out = np.empty((n, 32), dtype=np.uint8)
    lib.sha256_pairs(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_uint64(n),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
    )
    return out
