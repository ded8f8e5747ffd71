"""ctypes bindings to the native I/O runtime (native/mxtpu_io.cc).

Reference: the C++ data path (dmlc recordio + OMP JPEG decode,
``src/io/iter_image_recordio_2.cc``).  The library is built on demand with
g++ and cached next to the source; every entry point has a pure-Python
fallback so the framework works without a toolchain — taken with one
logged warning that carries the compiler's message, never in silence.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading

import numpy as np

_LIB = None
_TRIED = False
_LOCK = threading.Lock()

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SRC = os.path.join(_SRC_DIR, "mxtpu_io.cc")
_SO = os.path.join(_SRC_DIR, "libmxtpu_io.so")


def _build():
    # compile to a temp path and rename atomically so a concurrent process
    # never CDLLs a partially written .so
    tmp = "%s.%d.tmp" % (_SO, os.getpid())
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC,
           "-o", tmp, "-ljpeg", "-lpthread"]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, _SO)


def get_lib():
    """Load (building if needed) the native library, or None — the
    failure is logged once, with the compiler's message."""
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        try:
            if not os.path.isfile(_SO) or \
                    os.path.getmtime(_SO) < os.path.getmtime(_SRC):
                _build()
            lib = ctypes.CDLL(_SO)
            lib.mxtpu_recordio_index.restype = ctypes.c_long
            lib.mxtpu_recordio_index.argtypes = [
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_long),
                ctypes.c_long]
            lib.mxtpu_recordio_read.restype = ctypes.c_long
            lib.mxtpu_recordio_read.argtypes = [
                ctypes.c_char_p, ctypes.c_long,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_long]
            lib.mxtpu_decode_batch.restype = ctypes.c_long
            lib.mxtpu_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_long), ctypes.c_long,
                ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int]
            if lib.mxtpu_version() < 1:
                raise OSError("%s reports version %d" %
                              (_SO, lib.mxtpu_version()))
            _LIB = lib
        except (OSError, subprocess.CalledProcessError,
                AttributeError) as e:
            compiler = getattr(e, "stderr", None) or b""
            logging.getLogger(__name__).warning(
                "native I/O library unavailable, falling back to the "
                "pure-Python record reader and decoder: %s\n%s", e,
                compiler.decode(errors="replace").strip()[-2000:])
            _LIB = None
        return _LIB


def available():
    return get_lib() is not None


def recordio_index(path):
    """Record offsets of a .rec file via the native scanner (fast path)."""
    lib = get_lib()
    if lib is None:
        return None
    n = lib.mxtpu_recordio_index(path.encode(), None, 0)
    if n < 0:
        return None
    offsets = (ctypes.c_long * n)()
    lib.mxtpu_recordio_index(path.encode(), offsets, n)
    return list(offsets)


_read_buf = None
_read_lock = threading.Lock()


def recordio_read(path, offset, max_len=1 << 22):
    """Read one record payload at a byte offset via the native reader.
    A module-level buffer is reused under a lock (pipelines run on
    background threads) and grown up to 64 MB when a record exceeds it."""
    global _read_buf
    lib = get_lib()
    if lib is None:
        return None
    with _read_lock:
        if _read_buf is None or len(_read_buf) < max_len:
            _read_buf = (ctypes.c_uint8 * max_len)()
        n = lib.mxtpu_recordio_read(path.encode(), offset, _read_buf,
                                    len(_read_buf))
        if n < 0 and len(_read_buf) < (1 << 26):
            # maybe just a too-small buffer: one retry at the 64 MB cap
            _read_buf = (ctypes.c_uint8 * (1 << 26))()
            n = lib.mxtpu_recordio_read(path.encode(), offset, _read_buf,
                                        len(_read_buf))
        if n < 0:
            return None
        return ctypes.string_at(_read_buf, n)


def decode_batch(buffers, out_h, out_w, channels=3, resize_short=0,
                 num_threads=0):
    """Parallel JPEG decode+resize+crop into an (N, H, W, C) uint8 array.
    `buffers` is a list of bytes objects.  Returns (array, n_failures) or
    None when the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(buffers)
    out = np.empty((n, out_h, out_w, channels), np.uint8)
    bufs = (ctypes.c_char_p * n)(*buffers)
    lens = (ctypes.c_long * n)(*[len(b) for b in buffers])
    if num_threads <= 0:
        num_threads = min(os.cpu_count() or 1, 16)
    fails = lib.mxtpu_decode_batch(
        bufs, lens, n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out_h, out_w, channels, resize_short, num_threads)
    return out, int(fails)
