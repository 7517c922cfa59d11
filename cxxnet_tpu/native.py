"""ctypes binding to the native C++ runtime (``native/``).

The native library provides the host-side hot path of the data pipeline
— BinaryPage packfile IO, libjpeg decode, and a multi-threaded ordered
decode pipeline (the reference keeps these in C++ too:
src/utils/io.h:254-326, src/utils/decoder.h:21-60,
src/io/iter_thread_imbin_x-inl.hpp). Python remains the control plane;
ctypes calls release the GIL so decode workers run truly parallel.

The library auto-builds from source on first use (``make -C native``)
and every entry point has a pure-Python fallback, so the framework works
without a toolchain — just slower on the imgbin path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
import threading
from typing import List, Optional

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LIB_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "lib", "libcxxnet_native.so")

_lock = threading.Lock()
_lib = None
_tried = False


def _configure(lib) -> None:
    c_u8p = ctypes.POINTER(ctypes.c_uint8)
    c_fp = ctypes.POINTER(ctypes.c_float)

    lib.cxn_decode_jpeg.restype = ctypes.c_int
    lib.cxn_decode_jpeg.argtypes = [
        c_u8p, ctypes.c_int64, ctypes.POINTER(c_fp),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.cxn_free.restype = None
    lib.cxn_free.argtypes = [ctypes.c_void_p]

    lib.cxn_packer_open.restype = ctypes.c_void_p
    lib.cxn_packer_open.argtypes = [ctypes.c_char_p]
    lib.cxn_packer_push.restype = ctypes.c_int
    lib.cxn_packer_push.argtypes = [ctypes.c_void_p, c_u8p, ctypes.c_int64]
    lib.cxn_packer_close.restype = ctypes.c_int
    lib.cxn_packer_close.argtypes = [ctypes.c_void_p]

    lib.cxn_reader_open.restype = ctypes.c_void_p
    lib.cxn_reader_open.argtypes = [ctypes.POINTER(ctypes.c_char_p),
                                    ctypes.c_int]
    lib.cxn_reader_next.restype = ctypes.c_int64
    lib.cxn_reader_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(c_u8p)]
    lib.cxn_reader_reset.restype = None
    lib.cxn_reader_reset.argtypes = [ctypes.c_void_p]
    lib.cxn_reader_close.restype = None
    lib.cxn_reader_close.argtypes = [ctypes.c_void_p]

    lib.cxn_loader_create.restype = ctypes.c_void_p
    lib.cxn_loader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int]
    lib.cxn_loader_before_first.restype = None
    lib.cxn_loader_before_first.argtypes = [ctypes.c_void_p]
    lib.cxn_loader_next.restype = ctypes.c_int
    lib.cxn_loader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(c_fp),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(c_u8p),
        ctypes.POINTER(ctypes.c_int64)]
    lib.cxn_loader_destroy.restype = None
    lib.cxn_loader_destroy.argtypes = [ctypes.c_void_p]


def _build() -> bool:
    src = os.path.join(_REPO, "native")
    if not os.path.exists(os.path.join(src, "Makefile")):
        return False
    try:
        subprocess.run(["make", "-C", src, "-j4"], check=True,
                       capture_output=True, timeout=300)
        return os.path.exists(_LIB_PATH)
    except (subprocess.SubprocessError, OSError):
        return False


def _load():
    """-> (lib or None, why): the one attempt ``get_lib`` makes."""
    if os.environ.get("CXXNET_TPU_NO_NATIVE"):
        return None, "CXXNET_TPU_NO_NATIVE is set"
    if not os.path.exists(_LIB_PATH) and not _build():
        return None, "%s is missing and `make -C native` failed" \
            % _LIB_PATH
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        _configure(lib)
        return lib, _LIB_PATH
    except OSError as e:
        return None, "loading %s failed: %s" % (_LIB_PATH, e)


def get_lib():
    """The loaded native library, building it on first use; None if
    unavailable (no toolchain / build failure — callers fall back to
    the Python readers). Says ONCE on stderr which loader is in use,
    so a slow feed is never silently the fallback."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _lib, why = _load()
        sys.stderr.write(
            "cxxnet_tpu.native: %s\n"
            % ("native loader in use (%s)" % why if _lib is not None
               else "Python loader in use (%s)" % why))
        return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# high-level wrappers


def decode_jpeg(buf: bytes) -> Optional[np.ndarray]:
    """JPEG bytes -> (3, h, w) float32 RGB, or None if the native decoder
    is unavailable / the input is not a decodable JPEG."""
    lib = get_lib()
    if lib is None:
        return None
    out = ctypes.POINTER(ctypes.c_float)()
    c = ctypes.c_int()
    h = ctypes.c_int()
    w = ctypes.c_int()
    arr = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf)
    ok = lib.cxn_decode_jpeg(
        ctypes.cast(arr, ctypes.POINTER(ctypes.c_uint8)), len(buf),
        ctypes.byref(out), ctypes.byref(c), ctypes.byref(h),
        ctypes.byref(w))
    if not ok:
        return None
    n = c.value * h.value * w.value
    res = np.ctypeslib.as_array(out, shape=(n,)).reshape(
        c.value, h.value, w.value).copy()
    lib.cxn_free(ctypes.cast(out, ctypes.c_void_p))
    return res


class NativePacker:
    """BinaryPage packfile writer (native im2bin path)."""

    def __init__(self, path: str) -> None:
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.cxn_packer_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    # a fresh page holds (kPageSize - 2) ints minus one 4-byte offset slot
    MAX_OBJ = (64 << 18) * 4 - 12

    def push(self, obj: bytes) -> None:
        if len(obj) > self.MAX_OBJ:
            raise ValueError(
                "object of %d bytes exceeds page capacity" % len(obj))
        arr = (ctypes.c_uint8 * len(obj)).from_buffer_copy(obj)
        ok = self._lib.cxn_packer_push(
            self._h, ctypes.cast(arr, ctypes.POINTER(ctypes.c_uint8)),
            len(obj))
        if not ok:
            raise IOError("packfile write failed (disk full?)")

    def close(self) -> None:
        if self._h:
            ok = self._lib.cxn_packer_close(self._h)
            self._h = None
            if not ok:
                raise IOError("packfile final write failed (disk full?)")

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


def iter_packfile_native(paths: List[str]):
    """Yield every object across packfiles in order (native reader)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    cpaths = (ctypes.c_char_p * len(paths))(
        *[p.encode() for p in paths])
    h = lib.cxn_reader_open(cpaths, len(paths))
    try:
        buf = ctypes.POINTER(ctypes.c_uint8)()
        while True:
            n = lib.cxn_reader_next(h, ctypes.byref(buf))
            if n == 0:
                return
            yield ctypes.string_at(buf, n)
    finally:
        lib.cxn_reader_close(h)


class NativeDecodeLoader:
    """Ordered multi-threaded packfile decode pipeline.

    Yields (3, h, w) float32 RGB arrays in packfile order; objects the
    native decoder cannot handle (non-JPEG) come back as raw bytes and
    are decoded by the caller's Python fallback.
    """

    def __init__(self, bin_paths: List[str], nthread: int = 4,
                 capacity: int = 64) -> None:
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._paths = list(bin_paths)
        cpaths = (ctypes.c_char_p * len(self._paths))(
            *[p.encode() for p in self._paths])
        self._h = lib.cxn_loader_create(cpaths, len(self._paths),
                                        nthread, capacity)

    def before_first(self) -> None:
        self._lib.cxn_loader_before_first(self._h)

    def next(self):
        """(kind, value): ('img', ndarray) | ('raw', bytes) | (None, None)
        at end."""
        data = ctypes.POINTER(ctypes.c_float)()
        c = ctypes.c_int()
        h = ctypes.c_int()
        w = ctypes.c_int()
        raw = ctypes.POINTER(ctypes.c_uint8)()
        raw_len = ctypes.c_int64()
        st = self._lib.cxn_loader_next(
            self._h, ctypes.byref(data), ctypes.byref(c), ctypes.byref(h),
            ctypes.byref(w), ctypes.byref(raw), ctypes.byref(raw_len))
        if st == 0:
            return None, None
        if st == 1:
            n = c.value * h.value * w.value
            arr = np.ctypeslib.as_array(data, shape=(n,)).reshape(
                c.value, h.value, w.value).copy()
            return "img", arr
        return "raw", ctypes.string_at(raw, raw_len.value)

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.cxn_loader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
