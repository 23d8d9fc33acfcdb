"""Global flags registry.

Reference parity: gflags + PADDLE_DEFINE_EXPORTED_* (paddle/phi/core/flags.cc,
~95 flags), exported to python via pybind/global_value_getter_setter.cc and
paddle.set_flags/get_flags (python/paddle/fluid/framework.py:7764). Here: one
typed python registry; `FLAGS_*` environment variables are honored at import.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, Optional

from .. import _paths

_lock = threading.Lock()


class _Flag:
    __slots__ = ("name", "value", "default", "type", "help", "on_change")

    def __init__(self, name, default, type_, help_, on_change=None):
        self.name = name
        self.default = default
        self.value = default
        self.type = type_
        self.help = help_
        self.on_change = on_change


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(type_, raw):
    if type_ is bool and isinstance(raw, str):
        return raw.lower() in ("1", "true", "yes", "on")
    return type_(raw)


def define_flag(name: str, default: Any, help: str = "",
                type: Optional[Callable] = None,
                on_change: Optional[Callable[[Any], None]] = None):
    """Register a flag. `FLAGS_<name>` env var overrides the default."""
    type_ = type or (default.__class__ if default is not None else str)
    flag = _Flag(name, default, type_, help, on_change)
    env = os.environ.get("FLAGS_" + name)
    if env is not None:
        flag.value = _coerce(type_, env)
    with _lock:
        _REGISTRY[name] = flag
    return flag


def set_flags(flags: Dict[str, Any]):
    """paddle.set_flags parity (fluid/framework.py:7764)."""
    for name, value in flags.items():
        key = name[6:] if name.startswith("FLAGS_") else name
        with _lock:
            if key not in _REGISTRY:
                raise KeyError(f"Unknown flag: {name}")
            flag = _REGISTRY[key]
            flag.value = _coerce(flag.type, value)
        if flag.on_change is not None:
            flag.on_change(flag.value)


def get_flags(flags=None) -> Dict[str, Any]:
    """paddle.get_flags parity (fluid/framework.py:7789)."""
    if flags is None:
        names = list(_REGISTRY)
    elif isinstance(flags, str):
        names = [flags]
    else:
        names = list(flags)
    out = {}
    for name in names:
        key = name[6:] if name.startswith("FLAGS_") else name
        out["FLAGS_" + key] = _REGISTRY[key].value
    return out


def flag_value(name: str) -> Any:
    return _REGISTRY[name].value


# ---- Core flags (subset of paddle/phi/core/flags.cc relevant on TPU) ----
define_flag("check_nan_inf", False, "Per-op output nan/inf scan (debug).")
define_flag("check_nan_inf_level", 0, "0: abort on nan/inf; >=1: log only.")
define_flag("benchmark", False, "Synchronize after each op for timing.")
define_flag("cudnn_deterministic", False, "Deterministic kernels (XLA flag passthrough).")
define_flag("use_persistent_compilation_cache", True,
            "Enable jax persistent compilation cache.")
define_flag("compilation_cache_dir", _paths.cache_path("jax"),
            "Persistent XLA compilation cache directory when "
            "JAX_COMPILATION_CACHE_DIR is unset (paddle_tpu/_paths.py).")
define_flag("eager_log_level", 0, "Verbosity of eager runtime logging.")
