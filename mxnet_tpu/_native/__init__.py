"""Native (C++) runtime components, built on demand.

Reference parity: the reference's native layer (``src/io/``, ``src/engine``
thread pools).  The compute path needs no native code on TPU (XLA is the
native path); this package holds the host-side hot paths: the recordio
byte scanner and a GIL-free threaded prefetch ring (``io_core.cpp``).

The shared library is built from ``io_core.cpp`` on first use (g++ -O2,
~1s) into a git-ignored file next to the source, and rebuilt whenever
the source's content no longer matches the digest compiled into it — a
plain copy of the tree has arbitrary mtimes.  Which implementation is in
use is logged once; set ``MXNET_NATIVE_DISABLE=1`` to force the
pure-Python fallbacks.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger("mxnet_tpu._native")

_LIB = None
_LOCK = threading.Lock()
_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "io_core.cpp")
_OUT = os.path.join(_DIR, "libmxtpu_io.local.so")


def _build(src, out, digest):
    # minimal containers ship a C toolchain without g++; the gcc (or
    # cc) driver still compiles .cpp as C++ — it just doesn't link
    # libstdc++ on its own
    flags = ["-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
             '-DMXTPU_SRC_SHA256="%s"' % digest]
    # several processes (test workers) may build at once: each into its
    # own file, committed by an atomic rename
    tmp = "%s.%d.tmp" % (out, os.getpid())
    last = None
    try:
        for cmd in (["g++"] + flags + [src, "-o", tmp],
                    ["gcc"] + flags + [src, "-o", tmp, "-lstdc++"],
                    ["cc"] + flags + [src, "-o", tmp, "-lstdc++"]):
            try:
                subprocess.run(cmd, check=True, capture_output=True)
                os.replace(tmp, out)
                return
            except (OSError, subprocess.CalledProcessError) as e:
                last = e
        raise last
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load():
    """Build if stale, load, and declare the C signatures."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()
    stale = True
    if os.path.exists(_OUT):
        with open(_OUT, "rb") as f:
            stale = digest.encode() not in f.read()
    if stale:
        _build(_SRC, _OUT, digest)
    lib = ctypes.CDLL(_OUT)
    lib.mxtpu_rec_open.restype = ctypes.c_void_p
    lib.mxtpu_rec_open.argtypes = [ctypes.c_char_p]
    lib.mxtpu_rec_count.restype = ctypes.c_int64
    lib.mxtpu_rec_count.argtypes = [ctypes.c_void_p]
    lib.mxtpu_rec_length.restype = ctypes.c_int64
    lib.mxtpu_rec_length.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxtpu_rec_read.restype = ctypes.c_int64
    lib.mxtpu_rec_read.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.c_char_p, ctypes.c_int64]
    lib.mxtpu_rec_close.argtypes = [ctypes.c_void_p]
    lib.mxtpu_prefetch_start.restype = ctypes.c_void_p
    lib.mxtpu_prefetch_start.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32]
    lib.mxtpu_prefetch_next.restype = ctypes.c_int64
    lib.mxtpu_prefetch_next.argtypes = [ctypes.c_void_p,
                                        ctypes.c_char_p,
                                        ctypes.c_int64]
    lib.mxtpu_prefetch_stop.argtypes = [ctypes.c_void_p]
    return lib


def get_lib():
    """The loaded io_core library, or None if unavailable/disabled."""
    global _LIB
    if os.environ.get("MXNET_NATIVE_DISABLE") == "1":
        return None
    with _LOCK:
        if _LIB is None:
            try:
                _LIB = _load()
                log.info("recordio reads use the native io_core (%s)",
                         _OUT)
            except (OSError, subprocess.CalledProcessError,
                    AttributeError) as e:
                # a timing of the input pipeline would time Python:
                # say so where nobody can miss it
                _LIB = "failed"
                log.warning(
                    "native io_core could not be built or loaded (%s: "
                    "%s) — recordio reads fall back to the PURE-PYTHON "
                    "reader for the rest of this process",
                    type(e).__name__,
                    (getattr(e, "stderr", b"") or b"").decode(
                        errors="replace")[-400:] or e)
        return _LIB if _LIB != "failed" else None


class NativeRecordFile:
    """mmap-backed indexed recordio reader (no .idx needed — the index is
    rebuilt by a native scan at open)."""

    def __init__(self, path):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native io_core unavailable")
        self._lib = lib
        self._h = lib.mxtpu_rec_open(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def __len__(self):
        return self._lib.mxtpu_rec_count(self._h)

    def read(self, idx):
        n = self._lib.mxtpu_rec_length(self._h, idx)
        if n < 0:
            raise IndexError(idx)
        buf = ctypes.create_string_buffer(n)
        r = self._lib.mxtpu_rec_read(self._h, idx, buf, n)
        if r < 0:
            raise IOError("read failed")
        return buf.raw[:r]

    def prefetch(self, order, num_threads=4, depth=64):
        return NativePrefetcher(self, order, num_threads, depth)

    def close(self):
        if self._h:
            self._lib.mxtpu_rec_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativePrefetcher:
    """Iterator over records in a given order, loaded by C++ threads."""

    def __init__(self, recfile, order, num_threads=4, depth=64):
        self._lib = recfile._lib
        self._rec = recfile
        arr = (ctypes.c_int64 * len(order))(*order)
        self._max_len = max((recfile._lib.mxtpu_rec_length(recfile._h, i)
                             for i in order), default=0)
        self._h = self._lib.mxtpu_prefetch_start(
            recfile._h, arr, len(order), num_threads, depth)
        self._buf = ctypes.create_string_buffer(max(self._max_len, 1))

    def __iter__(self):
        return self

    def __next__(self):
        n = self._lib.mxtpu_prefetch_next(self._h, self._buf,
                                          len(self._buf))
        if n == -2:
            raise StopIteration
        if n < 0:
            raise IOError("prefetch read failed")
        return self._buf.raw[:n]

    def close(self):
        if self._h:
            self._lib.mxtpu_prefetch_stop(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
