"""Native (C++) input-pipeline kernels — build + ctypes binding.

The reference hid preprocessing cost in a spawned loader process
(reference: ``lib/proc_load_mpi.py`` — hkl load, img_mean subtract,
random crop, mirror in numpy; SURVEY.md §3.4), with hwloc pinning the
loader near its GPU (``lib/hwloc_utils.py``). The TPU rebuild keeps the
prefetch thread but makes the hot loop itself native: ``loader.cpp`` is
compiled ON DEMAND with the system g++ into ``_tmpi_native-<key>.so``
beside the source and called through ctypes — no build-system
dependency, and any failure degrades to the numpy path (``available()``
returns False). ``<key>`` hashes the source bytes, the compiler flags
and this CPU's feature flags, so a library is only ever loaded by a
checkout whose source produced it, on a CPU it was built for; a copied
tree or a shared filesystem rebuilds (~1 s) instead of running another
machine's ``-march=native`` code (SIGILL).

Set ``TMPI_NATIVE=0`` to force the numpy fallback;
``TMPI_LOADER_THREADS`` overrides the preprocessing thread count
(default: this process's CPU affinity count, capped at 8).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "loader.cpp")
_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def default_threads() -> int:
    """Loader thread count: the hwloc-equivalent default is the CPUs
    this process is actually bound to (respects container/taskset
    limits), capped — preprocessing should not starve the controller."""
    env = os.environ.get("TMPI_LOADER_THREADS")
    if env:
        return max(1, int(env))
    try:
        n = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        n = os.cpu_count() or 1
    return max(1, min(8, n - 1))


def _cpu_flags() -> str:
    """This CPU's feature flags (what -march=native compiles against)."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    import platform

    return platform.machine() + platform.processor()


def _artifact() -> Optional[str]:
    """Path of the library THIS source builds on THIS CPU; None when the
    source is missing (then nothing on disk can be trusted)."""
    import hashlib

    try:
        with open(_SRC, "rb") as f:
            src = f.read()
    except OSError:
        return None
    key = hashlib.sha256(
        src + " ".join(_FLAGS).encode() + _cpu_flags().encode()
    ).hexdigest()[:16]
    return os.path.join(_DIR, f"_tmpi_native-{key}.so")


def _build() -> Optional[str]:
    """Path of a loadable library built from this checkout's source, or
    None."""
    so = _artifact()
    if so is None or os.path.exists(so):
        return so
    # pid-unique tmp: N controller processes on one host may race to
    # build on first use; each compiles privately, os.replace is atomic,
    # last writer wins with a valid artifact either way
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, _SRC, "-lpthread"]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if r.returncode != 0:
            return None
        os.replace(tmp, so)
    except (OSError, subprocess.TimeoutExpired):
        return None
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return so


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("TMPI_NATIVE", "1") == "0":
            return None
        so = _build()
        if so is None:
            print(
                "theanompi_tpu.native: C++ loader kernels unavailable "
                "(g++/source missing?) — using the slower numpy path",
                flush=True,
            )
            return None
        try:
            lib = ctypes.CDLL(so)
            _bind(lib)
        except (OSError, AttributeError):
            print(
                f"theanompi_tpu.native: failed to load/bind {so} — using "
                "the slower numpy path",
                flush=True,
            )
            return None
        _lib = lib
        return _lib


def _bind(lib: ctypes.CDLL) -> None:
        lib.tmpi_crop_mirror_normalize.restype = ctypes.c_int
        lib.tmpi_crop_mirror_normalize.argtypes = [
            ctypes.c_void_p,  # in u8
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p,  # oy i32
            ctypes.c_void_p,  # ox i32
            ctypes.c_void_p,  # flip u8
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p,  # mean f32
            ctypes.c_int64,
            ctypes.c_float,
            ctypes.c_void_p,  # out f32
            ctypes.c_int,
        ]
        lib.tmpi_crop_mirror_u8.restype = ctypes.c_int
        lib.tmpi_crop_mirror_u8.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.tmpi_gather_rows.restype = ctypes.c_int
        lib.tmpi_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int,
        ]


def available() -> bool:
    return _load() is not None


def describe() -> str:
    """Which implementation feeds callers in this process, for logs."""
    lib = _load()
    if lib is None:
        return "numpy fallback"
    return f"native C++ ({os.path.basename(lib._name)})"


def crop_mirror_normalize(
    images: np.ndarray,  # uint8 [n, h, w, c]
    oy: np.ndarray,
    ox: np.ndarray,
    flip: np.ndarray,
    crop: int,
    mean: np.ndarray,  # f32 scalar [1] / per-channel [c] / plane [crop,crop,c]
    scale: float,
    n_threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Fused (u8 - mean) * scale with per-image crop+mirror. Returns the
    float32 batch, or None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n, h, w, c = images.shape
    images = np.ascontiguousarray(images)
    oy32 = np.ascontiguousarray(oy, dtype=np.int32)
    ox32 = np.ascontiguousarray(ox, dtype=np.int32)
    flip8 = np.ascontiguousarray(flip, dtype=np.uint8)
    mean32 = np.ascontiguousarray(mean, dtype=np.float32).reshape(-1)
    out = np.empty((n, crop, crop, c), dtype=np.float32)
    rc = lib.tmpi_crop_mirror_normalize(
        images.ctypes.data, n, h, w, c,
        oy32.ctypes.data, ox32.ctypes.data, flip8.ctypes.data,
        crop, crop,
        mean32.ctypes.data, mean32.size,
        ctypes.c_float(scale),
        out.ctypes.data,
        int(n_threads if n_threads is not None else default_threads()),
    )
    if rc != 0:
        raise ValueError(f"tmpi_crop_mirror_normalize failed (rc={rc})")
    return out


def crop_mirror_u8(
    images: np.ndarray,  # uint8 [n, h, w, c]
    oy: np.ndarray,
    ox: np.ndarray,
    flip: np.ndarray,
    crop: int,
    n_threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Per-image crop+mirror staying in uint8 (device-normalize
    pipeline: the (x - mean) * scale runs on-TPU, the host ships 4x
    fewer bytes). None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    n, h, w, c = images.shape
    images = np.ascontiguousarray(images)
    oy32 = np.ascontiguousarray(oy, dtype=np.int32)
    ox32 = np.ascontiguousarray(ox, dtype=np.int32)
    flip8 = np.ascontiguousarray(flip, dtype=np.uint8)
    out = np.empty((n, crop, crop, c), dtype=np.uint8)
    rc = lib.tmpi_crop_mirror_u8(
        images.ctypes.data, n, h, w, c,
        oy32.ctypes.data, ox32.ctypes.data, flip8.ctypes.data,
        crop, crop,
        out.ctypes.data,
        int(n_threads if n_threads is not None else default_threads()),
    )
    if rc != 0:
        raise ValueError(f"tmpi_crop_mirror_u8 failed (rc={rc})")
    return out


def gather_rows(
    source: np.ndarray,  # uint8-viewable [n_total, ...] (mmap ok)
    idx: np.ndarray,
    n_threads: Optional[int] = None,
) -> Optional[np.ndarray]:
    """Contiguous ``source[idx]`` via multithreaded memcpy (mmap shard ->
    batch assembly). Returns None when the native library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    if source.dtype != np.uint8 or not source.flags.c_contiguous:
        return None
    row_bytes = int(np.prod(source.shape[1:]))
    idx64 = np.ascontiguousarray(idx, dtype=np.int64)
    out = np.empty((len(idx64), *source.shape[1:]), dtype=np.uint8)
    rc = lib.tmpi_gather_rows(
        source.ctypes.data, row_bytes,
        idx64.ctypes.data, len(idx64),
        out.ctypes.data,
        int(n_threads if n_threads is not None else default_threads()),
    )
    if rc != 0:
        raise ValueError(f"tmpi_gather_rows failed (rc={rc})")
    return out
