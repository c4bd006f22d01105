"""ctypes bindings for the native IO library (libmxtpu_io.so).

The native layer is optional: on first use mxtpu runs ``make`` for it
when a toolchain is present — every time, so make's own mtime check
rebuilds a git-ignored binary that is older than its source instead of
loading it — and every consumer has a pure-Python fallback.
``available()`` reports whether the .so is loaded.
"""
from __future__ import annotations

import ctypes
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxtpu_io.so")
_lib = None


def _try_build():
    try:
        subprocess.run(["make", "-C", _DIR, "-s", os.path.basename(_SO)],
                       check=True, capture_output=True, timeout=120)
        return True
    except Exception:
        return False


def _load():
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("MXTPU_NO_NATIVE_BUILD", "0") != "1":
        _try_build()
    if not os.path.exists(_SO):
        return None
    lib = ctypes.CDLL(_SO)
    lib.rio_open_reader.restype = ctypes.c_void_p
    lib.rio_open_reader.argtypes = [ctypes.c_char_p]
    lib.rio_read_next.restype = ctypes.c_int64
    lib.rio_read_next.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_char_p)]
    lib.rio_read_at.restype = ctypes.c_int64
    lib.rio_read_at.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                ctypes.POINTER(ctypes.c_char_p)]
    lib.rio_reader_reset.argtypes = [ctypes.c_void_p]
    lib.rio_close_reader.argtypes = [ctypes.c_void_p]
    lib.rio_open_writer.restype = ctypes.c_void_p
    lib.rio_open_writer.argtypes = [ctypes.c_char_p]
    lib.rio_write.restype = ctypes.c_int64
    lib.rio_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                              ctypes.c_uint64]
    lib.rio_close_writer.argtypes = [ctypes.c_void_p]
    lib.pf_create.restype = ctypes.c_void_p
    lib.pf_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.pf_next.restype = ctypes.c_int64
    lib.pf_next.argtypes = [ctypes.c_void_p,
                            ctypes.POINTER(ctypes.c_char_p)]
    lib.pf_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def available():
    return _load() is not None


class NativeRecordReader:
    """Sequential native reader with the MXRecordIO interface subset."""

    def __init__(self, path):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._h = lib.rio_open_reader(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def read(self):
        buf = ctypes.c_char_p()
        n = self._lib.rio_read_next(self._h, ctypes.byref(buf))
        if n < 0:
            return None
        return ctypes.string_at(buf, n)

    def read_at(self, offset):
        buf = ctypes.c_char_p()
        n = self._lib.rio_read_at(self._h, offset, ctypes.byref(buf))
        if n < 0:
            return None
        return ctypes.string_at(buf, n)

    def reset(self):
        self._lib.rio_reader_reset(self._h)

    def close(self):
        if self._h:
            self._lib.rio_close_reader(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativeRecordWriter:
    def __init__(self, path):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._h = lib.rio_open_writer(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def write(self, buf):
        pos = self._lib.rio_write(self._h, buf, len(buf))
        if pos < 0:
            raise IOError("write failed")
        return pos

    def close(self):
        if self._h:
            self._lib.rio_close_writer(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativePrefetcher:
    """Background-thread record prefetcher (iter_prefetcher.h analogue)."""

    def __init__(self, path, capacity=64):
        lib = _load()
        if lib is None:
            raise RuntimeError("native IO library unavailable")
        self._lib = lib
        self._h = lib.pf_create(path.encode(), capacity)
        if not self._h:
            raise IOError("cannot open %s" % path)

    def __iter__(self):
        return self

    def __next__(self):
        buf = ctypes.c_char_p()
        n = self._lib.pf_next(self._h, ctypes.byref(buf))
        if n < 0:
            raise StopIteration
        return ctypes.string_at(buf, n)

    def close(self):
        if self._h:
            self._lib.pf_destroy(self._h)
            self._h = None

    def __del__(self):
        self.close()
