"""Device API.

Parity: python/paddle/device/ (set_device/get_device, cuda streams API).
TPU-first: devices are PJRT devices; streams/events are XLA's concern — the
API surface is kept for compatibility and maps onto jax device placement and
`block_until_ready` synchronization. Memory stats parity
(paddle.device.cuda.max_memory_allocated ← paddle/fluid/memory/stats.h:100)
comes from PJRT memory_stats.
"""
from __future__ import annotations

import jax

_current = None


def get_all_devices():
    """Device strings ("tpu:0", ...)."""
    return get_available_device()


def set_device(device):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' style strings."""
    global _current
    if isinstance(device, str):
        parts = device.split(":")
        kind = {"gpu": "tpu", "xpu": "tpu"}.get(parts[0], parts[0])
        idx = int(parts[1]) if len(parts) > 1 else 0
        # asking for a platform that is not there raises (jax.devices'
        # RuntimeError) and so does a bad index — never a silent
        # fallback to whatever device exists (core/tensor._resolve_device
        # holds place strings to the same rule)
        devs = jax.devices(kind)
        if not 0 <= idx < len(devs):
            raise ValueError(
                f"device index {idx} out of range for '{kind}' "
                f"({len(devs)} devices)")
        _current = devs[idx]
    else:
        _current = device
    return _current


def get_device():
    d = _current or jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def current_device():
    return _current or jax.devices()[0]


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    return True


def is_compiled_with_custom_device(name="tpu"):
    return name == "tpu"


def synchronize(device=None):
    """Block until all queued device work completes (jax dispatch is async)."""
    for d in jax.live_arrays():
        d.block_until_ready()


def max_memory_allocated(device=None):
    d = device if device is not None else current_device()
    try:
        stats = d.memory_stats()
        return stats.get("peak_bytes_in_use", 0)
    except Exception:
        return 0


def memory_allocated(device=None):
    d = device if device is not None else current_device()
    try:
        stats = d.memory_stats()
        return stats.get("bytes_in_use", 0)
    except Exception:
        return 0


def empty_cache():
    pass


# ---------------------------------------------------------------------------
# stream/event + exotic-place API shims. PJRT owns scheduling: programs
# run in submission order on the device's single logical stream, so the
# Stream/Event surface maps to synchronization points (reference:
# python/paddle/device/__init__.py Stream/Event over CUDA streams).
# ---------------------------------------------------------------------------

class Stream:
    """Parity: paddle.device.Stream — PJRT exposes one logical stream
    per device; wait/synchronize map to device synchronization."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def wait_event(self, event):
        synchronize()

    def wait_stream(self, stream):
        synchronize()

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def synchronize(self):
        synchronize()


class Event:
    """Parity: paddle.device.Event."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True  # submission-order execution: past work is done

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    """Parity: device.current_stream."""
    return _current_stream


def set_stream(stream):
    """Parity: device.set_stream."""
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    """Parity: device.stream_guard context manager."""

    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        self._prev = set_stream(self.stream)
        return self.stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


class XPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(xpu:{self.device_id})"


class IPUPlace:
    def __repr__(self):
        return "Place(ipu)"


class MLUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(mlu:{self.device_id})"


def get_cudnn_version():
    """Parity: device.get_cudnn_version — no CUDA runtime here."""
    return None


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_npu():
    return False


def get_all_device_type():
    """Parity: device.get_all_device_type."""
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()
                   if d.platform not in ("cpu", "gpu", "tpu")})


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in ("cpu", "gpu", "tpu")]


# submodule surfaces (paddle.device.cuda / paddle.device.xpu) — imported
# lazily at the bottom so they can re-use the functions above
from . import cuda  # noqa: E402,F401
from . import xpu   # noqa: E402,F401
