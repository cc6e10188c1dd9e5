"""ctypes binding for the native episode reader (native/episode_reader.cc).

The shared library is built on demand with g++ (no pybind11 needed). Arrays
backed by stored (uncompressed) members are zero-copy views into the mmap,
valid for the lifetime of the `NativeEpisode`; deflated members are owned
buffers. `load_episode_native` copies into regular numpy arrays by default
so callers never hold dangling views.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import logging
import os
import subprocess
import threading
from typing import Dict, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libepisode_reader.so")
_WS_LIB_PATH = os.path.join(_NATIVE_DIR, "libwindow_sampler.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False

_ws_lib = None
_ws_lock = threading.Lock()
_ws_build_failed = False


def _source_digest(src_path: str) -> str:
    with open(src_path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _is_current(lib_path: str, digest: str) -> bool:
    """True when `lib_path` exists and was built from source `digest`.

    The digest of the source each library was built from is recorded beside
    it (`<lib>.srchash`); mtimes are not consulted because a copied tree
    does not preserve them.
    """
    try:
        with open(lib_path + ".srchash") as f:
            return os.path.exists(lib_path) and f.read().strip() == digest
    except OSError:
        return False


def _build_lib(source: str, lib_path: str, link_flags=()) -> bool:
    """Ensure `lib_path` is built from the current `source`; compile if not.

    The freshness check runs BEFORE any write (a read-only install with a
    prebuilt current .so must work). Compilation happens under an flock so
    racing worker processes serialize, to a temp name atomically renamed so
    no process ever dlopens (or has mapped) a half-written .so. The commands
    mirror native/Makefile (kept for manual/dev builds). A failed build is
    logged with the compiler's output; callers then take the numpy path.
    """
    src_path = os.path.join(_NATIVE_DIR, source)
    try:
        digest = _source_digest(src_path)
        if _is_current(lib_path, digest):
            return True
        lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
        with open(lock_path, "w") as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            try:
                # Re-check under the lock: another process may have built.
                if not _is_current(lib_path, digest):
                    tmp = lib_path + f".tmp.{os.getpid()}"
                    subprocess.run(
                        [
                            "g++", "-O2", "-std=c++17", "-fPIC", "-Wall",
                            "-shared", src_path, *link_flags, "-o", tmp,
                        ],
                        check=True,
                        capture_output=True,
                    )
                    os.replace(tmp, lib_path)
                    with open(lib_path + ".srchash", "w") as f:
                        f.write(digest)
            finally:
                fcntl.flock(lock_f, fcntl.LOCK_UN)
        return True
    except subprocess.CalledProcessError as e:
        logging.warning(
            "native build of %s failed (rc=%d):\n%s",
            source, e.returncode, e.stderr.decode(errors="replace"),
        )
        return False
    except OSError as e:  # no g++, read-only tree, missing source
        logging.warning("native build of %s failed: %r", source, e)
        return False


def _build() -> bool:
    return _build_lib("episode_reader.cc", _LIB_PATH, ("-lz",))


def get_library() -> Optional[ctypes.CDLL]:
    """Load (building/rebuilding if needed) the library; None if unavailable."""
    global _lib, _build_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _build_failed = True
            return None
        lib.er_open.restype = ctypes.c_void_p
        lib.er_open.argtypes = [ctypes.c_char_p]
        lib.er_num_members.argtypes = [ctypes.c_void_p]
        lib.er_member_name.restype = ctypes.c_char_p
        lib.er_member_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.er_member_dtype.restype = ctypes.c_char_p
        lib.er_member_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.er_member_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.er_member_shape.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.er_member_data.restype = ctypes.c_void_p
        lib.er_member_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.er_member_nbytes.restype = ctypes.c_int64
        lib.er_member_nbytes.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.er_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return get_library() is not None


def get_window_sampler() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native window sampler; None if n/a."""
    global _ws_lib, _ws_build_failed
    with _ws_lock:
        if _ws_lib is not None:
            return _ws_lib
        if _ws_build_failed:
            return None
        if not _build_lib("window_sampler.cc", _WS_LIB_PATH, ("-lpthread",)):
            _ws_build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_WS_LIB_PATH)
        except OSError:
            _ws_build_failed = True
            return None
        lib.ws_crop_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_void_p,
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_int,
        ]
        # ws_packed_gather is absent from .so files built before the packed
        # cache landed; probe so a stale prebuilt library degrades to the
        # Python gather instead of an AttributeError mid-training.
        if hasattr(lib, "ws_packed_gather"):
            lib.ws_packed_gather.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_void_p,
                ctypes.c_int,
                ctypes.c_int,
                ctypes.c_int,
            ]
        _ws_lib = lib
        return _ws_lib


def sampler_available() -> bool:
    return (
        not os.environ.get("RT1_TPU_NO_NATIVE")
        and get_window_sampler() is not None
    )


def packed_gather_available() -> bool:
    """True when the built sampler exports the packed-format gather."""
    return sampler_available() and hasattr(
        get_window_sampler(), "ws_packed_gather"
    )


def packed_gather(
    frames: np.ndarray,
    frame_idx: np.ndarray,
    boxes: np.ndarray,
    out: np.ndarray,
    threads: int = 0,
) -> np.ndarray:
    """Gather n crops out of a packed (T, ph, pw, 3) uint8 frame block.

    frames: the episode's packed frames (typically an np.memmap);
    frame_idx: (n,) int64 frame indices; boxes: (n, 4) int32
    (top, left, crop_h, crop_w) in packed coordinates; out: (n, oh, ow, 3)
    uint8, written in place and returned. Crops already at (oh, ow) are
    strided memcpys (the packed-cache hot path); others bilinear-resample
    with cv2.INTER_LINEAR semantics. GIL-free and threaded like
    `crop_resize_batch`.
    """
    lib = get_window_sampler()
    if lib is None or not hasattr(lib, "ws_packed_gather"):
        raise RuntimeError("native packed gather unavailable")
    if frames.dtype != np.uint8 or frames.ndim != 4 or frames.shape[-1] != 3:
        raise ValueError(f"frames must be (T, ph, pw, 3) uint8, got "
                         f"{frames.dtype} {frames.shape}")
    if out.dtype != np.uint8 or not out.flags["C_CONTIGUOUS"]:
        raise ValueError("out must be C-contiguous uint8")
    n = len(frame_idx)
    t, ph, pw, _ = frames.shape
    idx = np.ascontiguousarray(frame_idx, np.int64)
    if n and (idx.min() < 0 or idx.max() >= t):
        raise IndexError(f"frame_idx out of range [0, {t})")
    boxes_arr = np.ascontiguousarray(boxes, np.int32)
    oh, ow = out.shape[1], out.shape[2]
    if n and (
        (boxes_arr[:, 0] < 0).any()
        or (boxes_arr[:, 1] < 0).any()
        or (boxes_arr[:, 0] + boxes_arr[:, 2] > ph).any()
        or (boxes_arr[:, 1] + boxes_arr[:, 3] > pw).any()
    ):
        raise IndexError("crop box out of packed-frame bounds")
    # np.memmap satisfies the buffer protocol; ctypes.data is the mapping.
    lib.ws_packed_gather(
        frames.ctypes.data_as(ctypes.c_void_p),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        boxes_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n,
        ph,
        pw,
        out.ctypes.data_as(ctypes.c_void_p),
        oh,
        ow,
        threads or (os.cpu_count() or 1),
    )
    return out


def crop_resize_batch(
    frames, boxes, out_h: int, out_w: int, threads: int = 0
) -> np.ndarray:
    """Crop+bilinear-resize a batch of frames in C++ (GIL-free, threaded).

    frames: sequence of (h, w, 3) uint8 arrays, all the same shape;
    boxes: (n, 4) int32 (top, left, crop_h, crop_w) per frame.
    Returns (n, out_h, out_w, 3) uint8. Matches cv2.INTER_LINEAR
    half-pixel-center semantics to +/-1 LSB.
    """
    lib = get_window_sampler()
    if lib is None:
        raise RuntimeError("native window sampler unavailable")
    n = len(frames)
    frames = [np.ascontiguousarray(f, np.uint8) for f in frames]
    h, w = frames[0].shape[:2]
    ptrs = (ctypes.c_void_p * n)(*[f.ctypes.data for f in frames])
    boxes_arr = np.ascontiguousarray(boxes, np.int32)
    out = np.empty((n, out_h, out_w, 3), np.uint8)
    lib.ws_crop_resize_batch(
        ptrs,
        boxes_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        n,
        h,
        w,
        out.ctypes.data_as(ctypes.c_void_p),
        out_h,
        out_w,
        threads or (os.cpu_count() or 1),
    )
    return out


_DTYPES = {
    "<f4": np.float32,
    "<f8": np.float64,
    "<i4": np.int32,
    "<i8": np.int64,
    "<u4": np.uint32,
    "<u8": np.uint64,
    "|u1": np.uint8,
    "|i1": np.int8,
    "|b1": np.bool_,
    "<f2": np.float16,
}


class NativeEpisode:
    """Handle over one open episode file; arrays are materialized on read."""

    def __init__(self, path: str):
        lib = get_library()
        if lib is None:
            raise RuntimeError("native episode reader unavailable")
        self._lib = lib
        self._handle = lib.er_open(path.encode())
        if not self._handle:
            raise IOError(f"native reader failed to open {path}")

    def keys(self):
        return [
            self._lib.er_member_name(self._handle, i).decode()
            for i in range(self._lib.er_num_members(self._handle))
        ]

    def _array(self, i: int, copy: bool = True) -> np.ndarray:
        descr = self._lib.er_member_dtype(self._handle, i).decode()
        dtype = _DTYPES.get(descr)
        if dtype is None:
            raise ValueError(f"unsupported dtype {descr!r}")
        ndim = self._lib.er_member_ndim(self._handle, i)
        shape = (ctypes.c_int64 * max(ndim, 1))()
        self._lib.er_member_shape(self._handle, i, shape)
        nbytes = self._lib.er_member_nbytes(self._handle, i)
        ptr = self._lib.er_member_data(self._handle, i)
        buf = (ctypes.c_char * nbytes).from_address(ptr)
        arr = np.frombuffer(buf, dtype=dtype).reshape(tuple(shape[:ndim]))
        return arr.copy() if copy else arr

    def to_dict(self, copy: bool = True) -> Dict[str, np.ndarray]:
        return {
            self._lib.er_member_name(self._handle, i).decode(): self._array(
                i, copy=copy
            )
            for i in range(self._lib.er_num_members(self._handle))
        }

    def close(self):
        if self._handle:
            self._lib.er_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def load_episode_native(path: str) -> Dict[str, np.ndarray]:
    """Drop-in native replacement for `episodes.load_episode`."""
    with NativeEpisode(path) as ep:
        return ep.to_dict(copy=True)
