"""Build helper for the C inference API (native/c_api.cc).

Reference role: paddle/fluid/inference/capi_exp/ — a C surface consumable
from C/Go. `build_c_api()` compiles libpaddle_capi.so on demand with the
embedding flags of the CURRENT interpreter (python3-config --embed), the
same on-demand pattern as the TCPStore/shm-ring natives.
"""
from __future__ import annotations

import os
import subprocess
import sysconfig
from typing import Optional

from .. import _paths

__all__ = ["build_c_api", "c_api_path"]

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native", "c_api.cc")
_CACHE_DIR = _paths.cache_path("native")
_SO = os.path.join(_CACHE_DIR, "libpaddle_capi.so")


def build_c_api(force: bool = False) -> Optional[str]:
    """Compile (if stale) and return the path of libpaddle_capi.so, or
    None when the toolchain is unavailable."""
    if not os.path.exists(_SRC):
        return None
    if not force and os.path.exists(_SO) and \
            os.path.getmtime(_SO) >= os.path.getmtime(_SRC):
        return _SO
    os.makedirs(_CACHE_DIR, exist_ok=True)
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_config_var(
        "VERSION")
    tmp = f"{_SO}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17", _SRC,
           f"-I{inc}", f"-L{libdir}", f"-lpython{ver}",
           f"-Wl,-rpath,{libdir}", "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, _SO)
        return _SO
    except (subprocess.SubprocessError, OSError):
        return None


def c_api_path() -> Optional[str]:
    # build_c_api already returns the cached .so when it is fresh and
    # rebuilds when the source is newer — no extra existence check here
    return build_c_api()
