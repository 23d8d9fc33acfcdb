"""ctypes binding for the native shared-memory SPSC ring (native/shm_ring.cc).

Reference role: shared-memory batch transport of the multiprocess
DataLoader (fluid/dataloader/worker.py shared-mem tensors +
operators/reader/buffered_reader.cc). One ring per worker; the parent
polls. Falls back to None when the toolchain is missing — callers keep the
mp.Queue path.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from .. import _paths

__all__ = ["ShmRing", "build_native_ring", "ring_available"]

_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native", "shm_ring.cc")
_CACHE_DIR = _paths.cache_path("native")
_SO_PATH = os.path.join(_CACHE_DIR, "libshm_ring.so")

_lib = None
_lib_lock = threading.Lock()


def build_native_ring(force: bool = False) -> Optional[str]:
    if not os.path.exists(_NATIVE_SRC):
        return None
    if not force and os.path.exists(_SO_PATH) and \
            os.path.getmtime(_SO_PATH) >= os.path.getmtime(_NATIVE_SRC):
        return _SO_PATH
    os.makedirs(_CACHE_DIR, exist_ok=True)
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"   # unique: no cross-proc race
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           _NATIVE_SRC, "-o", tmp, "-lrt"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO_PATH)
        return _SO_PATH
    except (subprocess.SubprocessError, OSError):
        return None


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = build_native_ring()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            # corrupt cached .so — rebuild once, else give up (callers
            # fall back to the mp.Queue transport)
            so = build_native_ring(force=True)
            if so is None:
                return None
            try:
                lib = ctypes.CDLL(so)
            except OSError:
                return None
        lib.psr_create.restype = ctypes.c_void_p
        lib.psr_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.psr_attach.restype = ctypes.c_void_p
        lib.psr_attach.argtypes = [ctypes.c_char_p]
        lib.psr_write.restype = ctypes.c_int
        lib.psr_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.c_uint64, ctypes.c_double]
        lib.psr_read.restype = ctypes.c_int64
        lib.psr_read.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
                                 ctypes.c_double]
        lib.psr_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
        lib.psr_mark_closed.argtypes = [ctypes.c_void_p]
        lib.psr_is_closed.restype = ctypes.c_int
        lib.psr_is_closed.argtypes = [ctypes.c_void_p]
        lib.psr_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        _lib = lib
        return _lib


def ring_available() -> bool:
    return _load_lib() is not None


class ShmRing:
    """SPSC byte-message ring over POSIX shm. One producer, one consumer."""

    def __init__(self, name: str, capacity: int = 64 << 20,
                 create: bool = True):
        lib = _load_lib()
        if lib is None:
            raise RuntimeError("native shm ring unavailable (no g++?)")
        self._lib = lib
        self.name = name
        self._owner = create
        if create:
            self._h = lib.psr_create(name.encode(), capacity)
        else:
            self._h = lib.psr_attach(name.encode())
        if not self._h:
            raise RuntimeError(f"shm ring {'create' if create else 'attach'}"
                               f" failed for {name!r}")

    def write(self, payload: bytes, timeout: float = 0.0) -> None:
        rc = self._lib.psr_write(self._h, payload, len(payload),
                                 float(timeout))
        if rc == -1:
            raise TimeoutError("shm ring write timed out")
        if rc == -2:
            raise BrokenPipeError("shm ring closed")
        if rc == -3:
            raise ValueError(
                f"message of {len(payload)} bytes exceeds ring capacity; "
                "raise DataLoader's shm capacity or shrink the batch")

    def read(self, timeout: float = 0.0) -> Optional[bytes]:
        """Next message; None on timeout; raises EOFError when closed and
        drained."""
        out = ctypes.POINTER(ctypes.c_char)()
        n = self._lib.psr_read(self._h, ctypes.byref(out), float(timeout))
        if n == -1:
            return None
        if n == -2:
            raise EOFError("shm ring closed")
        if n == -3:
            raise RuntimeError(
                "shm ring header corrupt or allocation failed "
                "(length word exceeds ring capacity)")
        try:
            return ctypes.string_at(out, n)
        finally:
            self._lib.psr_free(out)

    def mark_closed(self) -> None:
        if self._h:
            self._lib.psr_mark_closed(self._h)

    def close(self, unlink: Optional[bool] = None) -> None:
        if self._h:
            self._lib.psr_close(
                self._h, int(self._owner if unlink is None else unlink))
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
