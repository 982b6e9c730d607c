"""ctypes binding + lazy build for the native C++ augmentation pipeline.

The reference's native data path is NVIDIA DALI (C++/CUDA, SURVEY.md §2.4);
ours is ``data/native/image_pipeline.cpp`` — a multithreaded C++ kernel
producing two augmented float32 views per uint8 image with the canonical
augmentation spec.  This module compiles it on first use (g++, ~2s, cached
next to the source) and exposes numpy-in/numpy-out entry points; when no
toolchain or binary is available the loader silently stays on the tf.data
backend, so the native path is strictly opt-in acceleration
(``data_backend='native'``).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_SRC_DIR = os.path.join(os.path.dirname(__file__), "native")
_SRC = os.path.join(_SRC_DIR, "image_pipeline.cpp")


def _lib_path() -> str:
    """The binary's name carries a hash of its source: a copied tree can
    hold a stale ``.so`` whose mtime looks fresh (the binary is git-ignored
    and built where it runs), and a name that does not exist can only be
    built, never mistaken for current."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    return os.path.join(_SRC_DIR, f"libbyol_aug.{digest}.so")


_LIB = _lib_path()

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _build() -> None:
    base = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
            "-o", _LIB, _SRC]
    # Prefer the JPEG-fused build (libjpeg-turbo: fused decode+crop, the
    # DALI analog for image trees); fall back to the array-only build when
    # the system lacks jpeglib.h / -ljpeg.
    proc = subprocess.run(base + ["-DBYOL_WITH_JPEG", "-ljpeg"],
                          capture_output=True, text=True)
    if proc.returncode == 0:
        return
    proc = subprocess.run(base, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed: {proc.stderr[-2000:]}")


def load(rebuild: bool = False) -> ctypes.CDLL:
    """Load (building if needed) the native library; raises on failure."""
    global _lib, _build_error
    with _lock:
        if _lib is not None and not rebuild:
            return _lib
        if _build_error and not rebuild:
            raise RuntimeError(_build_error)
        try:
            if rebuild or not os.path.exists(_LIB):
                _build()
            lib = ctypes.CDLL(_LIB)
            u8p = ctypes.POINTER(ctypes.c_uint8)
            f32p = ctypes.POINTER(ctypes.c_float)
            lib.byol_augment_two_views.argtypes = [
                u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                f32p, f32p, ctypes.c_int, ctypes.c_float,
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int]
            lib.byol_augment_two_views.restype = None
            lib.byol_resize_batch.argtypes = [
                u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                f32p, ctypes.c_int, ctypes.c_int]
            lib.byol_resize_batch.restype = None
            lib.byol_has_jpeg.argtypes = []
            lib.byol_has_jpeg.restype = ctypes.c_int
            if lib.byol_has_jpeg():
                u64p = ctypes.POINTER(ctypes.c_uint64)
                i32p = ctypes.POINTER(ctypes.c_int32)
                lib.byol_jpeg_augment_two_views.argtypes = [
                    u8p, u64p, u64p, ctypes.c_int, f32p, f32p,
                    ctypes.c_int, ctypes.c_float, ctypes.c_uint64,
                    ctypes.c_uint64, ctypes.c_int, i32p]
                lib.byol_jpeg_augment_two_views.restype = None
                lib.byol_jpeg_resize_batch.argtypes = [
                    u8p, u64p, u64p, ctypes.c_int, f32p, ctypes.c_int,
                    ctypes.c_int, i32p]
                lib.byol_jpeg_resize_batch.restype = None
            _lib = lib
            _build_error = None
            return lib
        except Exception as e:  # toolchain missing, load failure, ...
            _build_error = str(e)
            raise


def available() -> bool:
    try:
        load()
        return True
    except Exception:
        return False


def _check_batch(images: np.ndarray) -> np.ndarray:
    if images.ndim != 4 or images.shape[-1] != 3:
        raise ValueError(f"expected (N, H, W, 3) uint8, got {images.shape}")
    return np.ascontiguousarray(images, dtype=np.uint8)


def augment_two_views(images: np.ndarray, size: int, *,
                      color_jitter_strength: float = 1.0, seed: int = 0,
                      index_base: int = 0,
                      num_threads: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """(N, H, W, 3) uint8 -> two (N, size, size, 3) float32 views in [0,1]."""
    lib = load()
    images = _check_batch(images)
    n, h, w, _ = images.shape
    if num_threads is None:
        num_threads = min(os.cpu_count() or 1, 16)
    v1 = np.empty((n, size, size, 3), np.float32)
    v2 = np.empty((n, size, size, 3), np.float32)
    lib.byol_augment_two_views(
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n, h, w,
        v1.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        v2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        size, float(color_jitter_strength), seed & (2**64 - 1),
        index_base & (2**64 - 1), num_threads)
    return v1, v2


def resize_batch(images: np.ndarray, size: int, *,
                 num_threads: Optional[int] = None) -> np.ndarray:
    """Resize-only eval transform (reference main.py:398, Quirk Q3)."""
    lib = load()
    images = _check_batch(images)
    n, h, w, _ = images.shape
    if num_threads is None:
        num_threads = min(os.cpu_count() or 1, 16)
    out = np.empty((n, size, size, 3), np.float32)
    lib.byol_resize_batch(
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), n, h, w,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), size,
        num_threads)
    return out


# ---- fused JPEG decode (the DALI-analog path for image trees) -------------

def has_jpeg() -> bool:
    """True when the loaded binary links libjpeg (fused decode available)."""
    try:
        return bool(load().byol_has_jpeg())
    except Exception:
        return False


def _pack_blobs(blobs) -> tuple:
    sizes = np.array([len(b) for b in blobs], np.uint64)
    offsets = np.zeros(len(blobs), np.uint64)
    np.cumsum(sizes[:-1], out=offsets[1:])
    blob = np.frombuffer(b"".join(blobs), np.uint8)
    return blob, offsets, sizes


def _decode_fallback(data: bytes) -> Optional[np.ndarray]:
    """PIL decode for the rare file the C++ path flags (non-JPEG extension
    lying about its content, CMYK, corrupt-but-PIL-tolerant)."""
    import io
    try:
        from PIL import Image
        return np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    except Exception:
        return None


def jpeg_augment_two_views(blobs, size: int, *,
                           color_jitter_strength: float = 1.0, seed: int = 0,
                           index_base: int = 0,
                           num_threads: Optional[int] = None
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """list of JPEG byte strings -> two (N, size, size, 3) float32 views.

    Fused decode+crop per view in C++ (only the sampled RandomResizedCrop
    window is decoded, DCT-scaled); files the native decoder rejects are
    re-decoded via PIL and fed through the uint8-array augment path with
    the SAME (seed, index, view) streams, so a mixed tree stays
    deterministic."""
    lib = load()
    if not lib.byol_has_jpeg():
        raise RuntimeError("native library built without libjpeg")
    n = len(blobs)
    if num_threads is None:
        num_threads = min(os.cpu_count() or 1, 16)
    blob, offsets, sizes = _pack_blobs(blobs)
    v1 = np.empty((n, size, size, 3), np.float32)
    v2 = np.empty((n, size, size, 3), np.float32)
    ok = np.empty((n,), np.int32)
    lib.byol_jpeg_augment_two_views(
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, v1.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        v2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        size, float(color_jitter_strength), seed & (2**64 - 1),
        index_base & (2**64 - 1), num_threads,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    for i in np.nonzero(ok == 0)[0]:
        img = _decode_fallback(blobs[i])
        if img is None:
            continue           # undecodable: keep the zeroed output
        a, b = augment_two_views(img[None], size,
                                 color_jitter_strength=color_jitter_strength,
                                 seed=seed, index_base=index_base + int(i),
                                 num_threads=1)
        v1[i], v2[i] = a[0], b[0]
    return v1, v2


def jpeg_resize_batch(blobs, size: int, *,
                      num_threads: Optional[int] = None) -> np.ndarray:
    """list of JPEG byte strings -> (N, size, size, 3) float32, resize-only
    (eval transform)."""
    lib = load()
    if not lib.byol_has_jpeg():
        raise RuntimeError("native library built without libjpeg")
    n = len(blobs)
    if num_threads is None:
        num_threads = min(os.cpu_count() or 1, 16)
    blob, offsets, sizes = _pack_blobs(blobs)
    out = np.empty((n, size, size, 3), np.float32)
    ok = np.empty((n,), np.int32)
    lib.byol_jpeg_resize_batch(
        blob.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), size,
        num_threads,
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    for i in np.nonzero(ok == 0)[0]:
        img = _decode_fallback(blobs[i])
        if img is None:
            continue
        out[i] = resize_batch(img[None], size, num_threads=1)[0]
    return out
