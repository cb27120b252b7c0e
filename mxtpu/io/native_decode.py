"""ctypes binding for the native decode pipeline (src/io/decode.cpp —
parity: the reference's C++ ImageRecordIOParser2 decode threads,
src/io/iter_image_recordio_2.cc).

The shared library is built on demand with the in-image g++ against the
system libjpeg the first time it is needed (and rebuilt when the source
is newer than the binary).  Where the toolchain or libjpeg is absent the
PIL path decodes instead — after one warning that says why, so a slow
input pipeline is never a silent one (``available()`` is the check).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

__all__ = ["available", "decode_jpeg", "decode_resize_batch"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.normpath(os.path.join(_HERE, "..", "..", "src", "io",
                                     "decode.cpp"))
_SO = os.path.join(_HERE, "_build", "libmxtpu_io.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _build():
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", _SO, "-ljpeg"]
    subprocess.run(cmd, check=True, capture_output=True, text=True)


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if not os.path.exists(_SRC):
                return None
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                _build()
            lib = ctypes.CDLL(_SO)
            lib.mxtpu_jpeg_dims.restype = ctypes.c_int
            lib.mxtpu_jpeg_dims.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int)]
            lib.mxtpu_decode_jpeg.restype = ctypes.c_int
            lib.mxtpu_decode_jpeg.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_void_p,
                ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int)]
            lib.mxtpu_decode_resize_batch.restype = ctypes.c_int
            lib.mxtpu_decode_resize_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_size_t), ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
                ctypes.c_int, ctypes.c_int]
            _lib = lib
        except (OSError, subprocess.CalledProcessError) as e:
            import warnings

            warnings.warn(
                "native JPEG decoder unavailable (%s: %s) — PIL decodes "
                "instead" % (type(e).__name__,
                             (getattr(e, "stderr", "") or str(e)).strip()
                             [-400:]), RuntimeWarning)
            _lib = None
        return _lib


def available() -> bool:
    return _load() is not None


def decode_jpeg(buf: bytes) -> np.ndarray:
    """Decode one JPEG to an RGB uint8 HWC array (native path)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    h, w = ctypes.c_int(), ctypes.c_int()
    rc = lib.mxtpu_jpeg_dims(buf, len(buf), ctypes.byref(h),
                             ctypes.byref(w))
    if rc:
        raise ValueError("not a decodable JPEG (rc=%d)" % rc)
    out = np.empty((h.value, w.value, 3), np.uint8)
    rc = lib.mxtpu_decode_jpeg(buf, len(buf),
                               out.ctypes.data_as(ctypes.c_void_p),
                               h.value, w.value, ctypes.byref(h),
                               ctypes.byref(w))
    if rc:
        raise ValueError("JPEG decode failed (rc=%d)" % rc)
    return out


def decode_resize_batch(bufs, out_h: int, out_w: int, n_threads: int = 0,
                        errors: str = "raise",
                        mode: str = "resize") -> np.ndarray:
    """Decode + transform a batch of JPEG byte strings to
    (N, out_h, out_w, 3) uint8, parallel across a native thread pool
    (the reference's per-batch decode-thread fan-out).

    mode='resize' is a plain bilinear resize; mode='center_crop'
    reproduces MXNet's CenterCropAug (scale_down + centered crop +
    resize — ImageRecordIter's default eval transform).
    errors='raise' (default) raises ValueError if any record fails;
    errors='zero' keeps the C layer's skip-corrupt-record contract
    (reference parser behavior): failed slots stay zero-filled and the
    good decodes are returned."""
    if errors not in ("raise", "zero"):
        raise ValueError("errors must be 'raise' or 'zero'")
    if mode not in ("resize", "center_crop"):
        raise ValueError("mode must be 'resize' or 'center_crop'")
    lib = _load()
    if lib is None:
        raise RuntimeError("native decoder unavailable")
    n = len(bufs)
    if n == 0:
        return np.empty((0, out_h, out_w, 3), np.uint8)
    if n_threads <= 0:
        n_threads = min(n, os.cpu_count() or 1)
    keep = [bytes(b) for b in bufs]  # own the memory across the call
    arr_bufs = (ctypes.c_char_p * n)(*keep)
    arr_lens = (ctypes.c_size_t * n)(*[len(b) for b in keep])
    out = np.empty((n, out_h, out_w, 3), np.uint8)
    failures = lib.mxtpu_decode_resize_batch(
        arr_bufs, arr_lens, n, out_h, out_w,
        out.ctypes.data_as(ctypes.c_void_p), n_threads,
        1 if mode == "center_crop" else 0)
    if failures and errors == "raise":
        raise ValueError("%d/%d records failed to decode" % (failures, n))
    return out
