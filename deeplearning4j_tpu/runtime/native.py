"""ctypes binding for the native IO runtime (native/dl4jtpu_io.cpp).

The reference's ETL hot paths are native (libnd4j buffer routines,
JavaCV-backed decoders behind DataVec — SURVEY.md §2.2); this module is
the TPU build's equivalent tier: CSV -> float32 matrices parsed
multithreaded in C++, IDX (MNIST-family) decoding, and uint8 -> float32
normalization at memory bandwidth.  Everything degrades gracefully — when
the shared library isn't built and can't be built (no toolchain), callers
fall back to their numpy paths.

    from deeplearning4j_tpu.runtime import native
    if native.available():
        arr = native.csv_read_f32("data.csv", skip_rows=1)
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_NAME = "libdl4jtpu_io.so"
ENV_DISABLE = "DL4JTPU_NO_NATIVE"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> None:
    """Run `make -C native` whenever the toolchain is there, so the
    library that loads is always built from the tracked source (make's
    own timestamps make this a no-op when the .so is current; a stale or
    foreign .so on disk is rebuilt instead of trusted).  Without the
    toolchain nothing is built and an existing library loads as is."""
    import logging
    import shutil

    if shutil.which("make") is None or shutil.which(
        os.environ.get("CXX", "g++")
    ) is None:
        return
    log = logging.getLogger(__name__)
    try:
        proc = subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            capture_output=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as exc:
        log.warning("native IO library build failed: %s", exc)
        return
    if proc.returncode != 0:
        log.warning("native IO library build failed (rc=%d): %s",
                    proc.returncode,
                    proc.stderr.decode(errors="replace")[-400:])


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get(ENV_DISABLE, "") not in ("", "0"):
            return None
        path = _NATIVE_DIR / _LIB_NAME
        _build()
        if not path.exists():
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError:
            return None
        lib.dl4jtpu_csv_read_f32.restype = ctypes.c_int
        lib.dl4jtpu_csv_read_f32.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_long,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
            ctypes.c_int,
        ]
        lib.dl4jtpu_idx_read_u8.restype = ctypes.c_int
        lib.dl4jtpu_idx_read_u8.argtypes = [
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_int), ctypes.c_long * 4,
        ]
        lib.dl4jtpu_u8_to_f32_scaled.restype = None
        lib.dl4jtpu_u8_to_f32_scaled.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
            ctypes.c_long, ctypes.c_float, ctypes.c_float, ctypes.c_int,
        ]
        lib.dl4jtpu_free.restype = None
        lib.dl4jtpu_free.argtypes = [ctypes.c_void_p]
        lib.dl4jtpu_io_version.restype = ctypes.c_char_p
        lib.dl4jtpu_has_jpeg.restype = ctypes.c_int
        lib.dl4jtpu_jpeg_batch.restype = ctypes.c_int
        lib.dl4jtpu_jpeg_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_long,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ]
        lib.dl4jtpu_jpeg_batch_u8.restype = ctypes.c_int
        lib.dl4jtpu_jpeg_batch_u8.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_long,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def version() -> Optional[str]:
    lib = _load()
    return lib.dl4jtpu_io_version().decode() if lib else None


def _n_threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


def csv_read_f32(path: str, delimiter: str = ",",
                 skip_rows: int = 0) -> np.ndarray:
    """Parse a numeric CSV into a float32 (rows, cols) array natively."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native IO library unavailable")
    data = ctypes.POINTER(ctypes.c_float)()
    rows = ctypes.c_long()
    cols = ctypes.c_long()
    rc = lib.dl4jtpu_csv_read_f32(
        str(path).encode(), delimiter.encode()[:1], skip_rows,
        ctypes.byref(data), ctypes.byref(rows), ctypes.byref(cols),
        _n_threads(),
    )
    if rc != 0:
        raise IOError(f"dl4jtpu_csv_read_f32({path}) failed rc={rc}")
    try:
        out = np.ctypeslib.as_array(
            data, shape=(rows.value, cols.value)
        ).copy()
    finally:
        lib.dl4jtpu_free(data)
    return out


def idx_read_u8(path: str) -> np.ndarray:
    """Decode an IDX file of unsigned bytes (MNIST images/labels)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native IO library unavailable")
    data = ctypes.POINTER(ctypes.c_uint8)()
    ndim = ctypes.c_int()
    dims = (ctypes.c_long * 4)()
    rc = lib.dl4jtpu_idx_read_u8(
        str(path).encode(), ctypes.byref(data), ctypes.byref(ndim), dims
    )
    if rc != 0:
        raise IOError(f"dl4jtpu_idx_read_u8({path}) failed rc={rc}")
    shape = tuple(dims[i] for i in range(ndim.value))
    try:
        out = np.ctypeslib.as_array(data, shape=shape).copy()
    finally:
        lib.dl4jtpu_free(data)
    return out


def u8_to_f32_scaled(src: np.ndarray, scale: float = 1.0 / 255.0,
                     shift: float = 0.0) -> np.ndarray:
    """uint8 -> float32 * scale + shift (image normalization hot path)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native IO library unavailable")
    src = np.ascontiguousarray(src, dtype=np.uint8)
    dst = np.empty(src.shape, np.float32)
    lib.dl4jtpu_u8_to_f32_scaled(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        src.size, scale, shift, _n_threads(),
    )
    return dst


def has_jpeg() -> bool:
    """True when the library was compiled against libjpeg."""
    lib = _load()
    return bool(lib is not None and lib.dl4jtpu_has_jpeg())


def jpeg_batch_decode(paths, height: int, width: int, channels: int = 3,
                      n_threads: int = 0, dtype=np.float32) -> np.ndarray:
    """Decode + resize a batch of JPEG files natively ->
    (n, height, width, channels) in 0..255 (the ImageRecordReader value
    convention).  libjpeg's DCT-domain prescale does most of the
    downscaling inside the IDCT; a bilinear pass lands the exact target.
    Files that fail to decode come back zero-filled (a warning is
    logged).

    dtype float32 (default) or uint8: uint8 is the WIRE format for the
    device-cast ETL path — 4x fewer host->device bytes, with the cast to
    the compute dtype running inside the jitted step."""
    import logging

    lib = _load()
    if lib is None or not has_jpeg():
        raise RuntimeError("native JPEG decode unavailable")
    dtype = np.dtype(dtype)
    if dtype not in (np.dtype(np.float32), np.dtype(np.uint8)):
        raise ValueError(f"jpeg_batch_decode dtype must be float32 or "
                         f"uint8, got {dtype}")
    paths = [str(p) for p in paths]
    n = len(paths)
    out = np.empty((n, height, width, channels), dtype)
    arr = (ctypes.c_char_p * n)(*(p.encode() for p in paths))
    if dtype == np.uint8:
        fails = lib.dl4jtpu_jpeg_batch_u8(
            arr, n, height, width, channels,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n_threads or _n_threads(),
        )
    else:
        fails = lib.dl4jtpu_jpeg_batch(
            arr, n, height, width, channels,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n_threads or _n_threads(),
        )
    if fails:
        logging.getLogger(__name__).warning(
            "jpeg_batch_decode: %d/%d files failed (zero-filled)", fails, n
        )
    return out
