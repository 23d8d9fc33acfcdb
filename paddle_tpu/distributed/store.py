"""TCPStore: KV rendezvous for multi-host process formation.

Parity: core.TCPStore (paddle/phi/core/distributed/store/tcp_store.h:120,
bound in pybind and consumed by init_parallel_env, parallel.py:1092). The
store itself is NATIVE C++ (native/tcp_store.cc — raw sockets, mutex+
condvar map, thread-per-connection master) mirroring the reference's
native store; Python binds it via ctypes (no pybind11 in this image). A
pure-python fallback keeps the API alive if the toolchain is missing.

Role on TPU (SURVEY.md §5.8): the XLA runtime forms the ICI world; this
store carries DCN-level coordination — JAX coordinator address exchange,
barriers, elastic heartbeats — exactly the jobs the reference gives it.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from .. import _paths
from . import resilience as _resil

__all__ = ["TCPStore", "build_native_store"]

_NATIVE_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native", "tcp_store.cc")
_CACHE_DIR = _paths.cache_path("native")
_SO_PATH = os.path.join(_CACHE_DIR, "libtcp_store.so")

_lib = None
_lib_lock = threading.Lock()


def build_native_store(force: bool = False) -> Optional[str]:
    """Compile native/tcp_store.cc into a shared object (cached)."""
    if not os.path.exists(_NATIVE_SRC):
        return None
    if not force and os.path.exists(_SO_PATH) and \
            os.path.getmtime(_SO_PATH) >= os.path.getmtime(_NATIVE_SRC):
        return _SO_PATH
    os.makedirs(_CACHE_DIR, exist_ok=True)
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
           _NATIVE_SRC, "-o", _SO_PATH + ".tmp"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(_SO_PATH + ".tmp", _SO_PATH)
        return _SO_PATH
    except (subprocess.SubprocessError, OSError):
        return None


def _load_lib():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        so = build_native_store()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        lib.pts_master_start.restype = ctypes.c_void_p
        lib.pts_master_start.argtypes = [ctypes.c_int,
                                         ctypes.POINTER(ctypes.c_int)]
        lib.pts_master_stop.argtypes = [ctypes.c_void_p]
        lib.pts_client_connect.restype = ctypes.c_void_p
        lib.pts_client_connect.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                           ctypes.c_int]
        lib.pts_client_close.argtypes = [ctypes.c_void_p]
        lib.pts_client_shutdown.argtypes = [ctypes.c_void_p]
        lib.pts_set.restype = ctypes.c_int
        lib.pts_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.c_char_p,
                                ctypes.c_uint32]
        lib.pts_get.restype = ctypes.c_int64
        lib.pts_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.c_int64,
                                ctypes.POINTER(ctypes.POINTER(ctypes.c_char))]
        lib.pts_add.restype = ctypes.c_int64
        lib.pts_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32, ctypes.c_int64,
                                ctypes.POINTER(ctypes.c_int)]
        lib.pts_wait.restype = ctypes.c_int
        lib.pts_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint32, ctypes.c_int64]
        lib.pts_del.restype = ctypes.c_int
        lib.pts_del.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint32]
        lib.pts_buf_free.argtypes = [ctypes.POINTER(ctypes.c_char)]
        _lib = lib
        return _lib


class _PyFallbackStore:
    """In-process fallback (single-host only) when g++ is unavailable."""

    def __init__(self):
        self._map = {}
        self._cv = threading.Condition()

    def set(self, key, value):
        with self._cv:
            self._map[key] = bytes(value)
            self._cv.notify_all()

    def get(self, key, timeout_s):
        with self._cv:
            ok = self._cv.wait_for(lambda: key in self._map, timeout_s)
            if not ok:
                raise TimeoutError(f"TCPStore.get({key!r}) timed out")
            return self._map[key]

    def add(self, key, delta):
        with self._cv:
            cur = int.from_bytes(self._map.get(key, b"\0" * 8), "little",
                                 signed=True)
            cur += delta
            self._map[key] = cur.to_bytes(8, "little", signed=True)
            self._cv.notify_all()
            return cur

    def wait(self, key, timeout_s):
        with self._cv:
            if not self._cv.wait_for(lambda: key in self._map, timeout_s):
                raise TimeoutError(f"TCPStore.wait({key!r}) timed out")

    def delete(self, key):
        with self._cv:
            self._map.pop(key, None)


_py_fallback_masters = {}


class TCPStore:
    """Parity: paddle.distributed's core.TCPStore(host, port, is_master,
    world_size, timeout)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 is_master: bool = False, world_size: int = 1,
                 timeout: float = 300.0):
        self.host = host
        self.timeout = timeout
        self._master_handle = None
        self._client = None
        self._py = None
        # one request/response in flight per connection: concurrent
        # threads (e.g. the elastic heartbeat) would interleave wire
        # frames and wedge both ends
        self._io_lock = threading.Lock()
        lib = _load_lib()
        if lib is None:
            # single-process fallback keyed by port
            self._py = _py_fallback_masters.setdefault(
                port, _PyFallbackStore())
            self.port = port
            return
        self._lib = lib
        if is_master:
            out_port = ctypes.c_int(0)
            self._master_handle = lib.pts_master_start(
                port, ctypes.byref(out_port))
            if not self._master_handle:
                raise RuntimeError(f"TCPStore master bind failed on {port}")
            self.port = out_port.value
        else:
            self.port = port

        # Rendezvous retry (resilience.RetryPolicy): workers routinely
        # race the master's bind — a refused connect is retried under
        # exponential backoff within the store's own timeout budget,
        # instead of failing the whole process formation on attempt 1.
        def _connect():
            c = lib.pts_client_connect(
                host.encode(), self.port, int(timeout * 1000))
            if not c:
                raise ConnectionError(
                    f"TCPStore connect to {host}:{self.port} failed")
            return c
        policy = _resil.RetryPolicy.from_env(
            "PADDLE_TPU_RENDEZVOUS", max_attempts=4, base_delay=0.25,
            max_delay=5.0, deadline=timeout,
            retry_on=(ConnectionError,))
        try:
            self._client = policy.run(_connect)
        except ConnectionError as e:
            raise RuntimeError(str(e)) from e

    def _conn(self):
        if self._client is None:
            raise RuntimeError("TCPStore is closed")
        return self._client

    # -- API (paddle Store surface: store.h:24) -------------------------
    def set(self, key: str, value) -> None:
        if self._py is not None:
            return self._py.set(key, _to_bytes(value))
        v = _to_bytes(value)
        k = key.encode()
        with self._io_lock:
            ok = self._lib.pts_set(self._conn(), k, len(k), v, len(v))
        if ok != 0:
            raise RuntimeError("TCPStore.set failed")

    def get(self, key: str) -> bytes:
        # fault site: a peer host dropping out of the job manifests as
        # a get/wait timing out on a key the dead rank never set
        _resil.maybe_inject("host_drop")
        if self._py is not None:
            return self._py.get(key, self.timeout)
        k = key.encode()
        out = ctypes.POINTER(ctypes.c_char)()
        with self._io_lock:
            n = self._lib.pts_get(self._conn(), k, len(k),
                                  int(self.timeout * 1000),
                                  ctypes.byref(out))
        if n == -1:
            raise TimeoutError(f"TCPStore.get({key!r}) timed out")
        if n < 0:
            raise RuntimeError("TCPStore.get socket error")
        data = ctypes.string_at(out, int(n)) if n > 0 else b""
        if n > 0:
            self._lib.pts_buf_free(out)
        return data

    def add(self, key: str, amount: int) -> int:
        if self._py is not None:
            return self._py.add(key, amount)
        k = key.encode()
        err = ctypes.c_int(0)
        with self._io_lock:
            val = self._lib.pts_add(self._conn(), k, len(k), amount,
                                    ctypes.byref(err))
        if err.value != 0:
            raise RuntimeError("TCPStore.add failed")
        return int(val)

    def wait(self, key: str) -> None:
        _resil.maybe_inject("host_drop")
        if self._py is not None:
            return self._py.wait(key, self.timeout)
        k = key.encode()
        with self._io_lock:
            r = self._lib.pts_wait(self._conn(), k, len(k),
                                   int(self.timeout * 1000))
        if r == -1:
            raise TimeoutError(f"TCPStore.wait({key!r}) timed out")
        if r != 0:
            raise RuntimeError("TCPStore.wait socket error")

    def delete_key(self, key: str) -> None:
        if self._py is not None:
            return self._py.delete(key)
        k = key.encode()
        with self._io_lock:
            self._lib.pts_del(self._conn(), k, len(k))

    # -- helpers ---------------------------------------------------------
    def barrier(self, name: str, world_size: int) -> None:
        """All `world_size` participants block until everyone arrived."""
        n = self.add(f"__barrier/{name}", 1)
        if n >= world_size:
            self.set(f"__barrier/{name}/done", b"1")
        self.wait(f"__barrier/{name}/done")

    def close(self):
        if self._py is not None:
            return
        # Ordered shutdown: briefly wait for an in-flight request to finish
        # (the server may apply a set and wake a blocked getter before
        # acking the setter — closing mid-request fails that call
        # spuriously). If another thread is parked in a long get/wait,
        # shutdown(2) the socket to abort it instead of blocking close for
        # the full store timeout, then take the lock and free.
        if not self._io_lock.acquire(timeout=0.5):
            if self._client is not None:
                self._lib.pts_client_shutdown(self._client)
            self._io_lock.acquire()
        try:
            if self._client is not None:
                self._lib.pts_client_close(self._client)
                self._client = None
            if self._master_handle is not None:
                self._lib.pts_master_stop(self._master_handle)
                self._master_handle = None
        finally:
            self._io_lock.release()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _to_bytes(v) -> bytes:
    if isinstance(v, bytes):
        return v
    if isinstance(v, str):
        return v.encode()
    if isinstance(v, int):
        return str(v).encode()
    return bytes(v)
