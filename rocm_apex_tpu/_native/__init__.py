"""ctypes binding for the host-native runtime ops (csrc/host_ops.cpp).

The analogue of importing the reference's compiled extensions with
python fallbacks on failure (reference: apex/parallel/distributed.py:
13-33 imports apex_C.flatten and falls back to torch._utils). The
shared library is built on first use with g++ into a file named by the
hash of the source, so a copied or checked-out tree (whose mtimes mean
nothing) can never load a binary built from other source. A failed
build warns once, with the compiler's output, and leaves the numpy
fallbacks active with ``available = False`` (the
multi_tensor_applier.available pattern,
apex/multi_tensor_apply/multi_tensor_apply.py:3-30).
"""

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

import numpy as np

__all__ = [
    "available",
    "flatten",
    "unflatten",
    "fast_collate",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "..", "..", "csrc", "host_ops.cpp")
_lib = None
_lock = threading.Lock()
available = False


def _build_and_load():
    global _lib, available
    with _lock:
        if _lib is not None:
            return _lib
        try:
            with open(_SRC, "rb") as f:
                digest = hashlib.sha256(f.read()).hexdigest()[:16]
            so = os.path.join(_HERE, f"_host_ops.{digest}.so")
            if not os.path.exists(so):
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    [
                        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
                        "-pthread", _SRC, "-o", tmp,
                    ],
                    check=True,
                    capture_output=True,
                )
                os.replace(tmp, so)
            lib = ctypes.CDLL(so)
            lib.apex_tpu_flatten.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_int,
            ]
            lib.apex_tpu_unflatten.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int,
            ]
            lib.apex_tpu_fast_collate.argtypes = [
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ]
            _lib = lib
            available = True
        except (OSError, subprocess.CalledProcessError) as e:
            _lib = False  # numpy fallbacks stay active
            available = False
            detail = getattr(e, "stderr", b"") or b""
            warnings.warn(
                "rocm_apex_tpu._native: building csrc/host_ops.cpp failed "
                f"({e!r}); flatten/unflatten/fast_collate run in numpy. "
                + detail.decode(errors="replace")[-2000:],
                RuntimeWarning,
                stacklevel=3,
            )
    return _lib


_DEFAULT_THREADS = min(8, os.cpu_count() or 1)


def _ptr_array(arrays):
    ptrs = (ctypes.c_void_p * len(arrays))()
    for i, a in enumerate(arrays):
        ptrs[i] = a.ctypes.data_as(ctypes.c_void_p)
    return ptrs


def flatten(arrays, threads: int = _DEFAULT_THREADS) -> np.ndarray:
    """Concatenate same-dtype numpy arrays into one flat buffer
    (reference apex_C.flatten)."""
    arrays = [np.ascontiguousarray(a) for a in arrays]
    if not arrays:
        return np.empty((0,), np.float32)
    dtype = arrays[0].dtype
    if any(a.dtype != dtype for a in arrays):
        raise TypeError("flatten requires uniform dtype")
    lib = _build_and_load()
    total = sum(a.size for a in arrays)
    out = np.empty((total,), dtype)
    if not lib:
        np.concatenate([a.ravel() for a in arrays], out=out)
        return out
    sizes = (ctypes.c_int64 * len(arrays))(*[a.size for a in arrays])
    lib.apex_tpu_flatten(
        _ptr_array(arrays), sizes, len(arrays), dtype.itemsize,
        out.ctypes.data_as(ctypes.c_void_p), threads,
    )
    return out


def unflatten(flat: np.ndarray, shapes, threads: int = _DEFAULT_THREADS):
    """Split a flat buffer back into arrays of `shapes`
    (reference apex_C.unflatten)."""
    flat = np.ascontiguousarray(flat)
    outs = [np.empty(s, flat.dtype) for s in shapes]
    lib = _build_and_load()
    if not lib:
        off = 0
        for o in outs:
            o.ravel()[:] = flat[off : off + o.size]
            off += o.size
        return outs
    sizes = (ctypes.c_int64 * len(outs))(*[o.size for o in outs])
    lib.apex_tpu_unflatten(
        flat.ctypes.data_as(ctypes.c_void_p), sizes, len(outs),
        flat.dtype.itemsize, _ptr_array(outs), threads,
    )
    return outs


def fast_collate(
    images,
    mean=None,
    std=None,
    threads: int = _DEFAULT_THREADS,
) -> np.ndarray:
    """uint8 HWC images -> float32 NHWC batch, optional per-channel
    (x/255 - mean)/std (reference: examples/imagenet fast_collate +
    normalization deferred to the prefetcher)."""
    images = [np.ascontiguousarray(im, np.uint8) for im in images]
    n = len(images)
    if n == 0:
        return np.empty((0,), np.float32)
    h, w, c = images[0].shape
    if any(im.shape != (h, w, c) for im in images):
        raise ValueError("fast_collate requires uniform image shapes")
    out = np.empty((n, h, w, c), np.float32)
    lib = _build_and_load()
    if not lib:
        batch = np.stack(images).astype(np.float32)
        if mean is not None and std is not None:
            batch = (batch / 255.0 - np.asarray(mean, np.float32)) / np.asarray(
                std, np.float32
            )
        out[...] = batch
        return out
    mean_p = std_p = None
    if mean is not None and std is not None:
        mean_a = np.ascontiguousarray(mean, np.float32)
        std_a = np.ascontiguousarray(std, np.float32)
        mean_p = mean_a.ctypes.data_as(ctypes.c_void_p)
        std_p = std_a.ctypes.data_as(ctypes.c_void_p)
    lib.apex_tpu_fast_collate(
        _ptr_array(images), n, h, w, c,
        out.ctypes.data_as(ctypes.c_void_p), mean_p, std_p, threads,
    )
    return out
