"""Where this checkout keeps what it builds at run time (leaf module —
stdlib only, no package imports).

Everything the program caches — XLA's persistent compilation cache, the
serialized-executable store, the native libraries built from
``native/*.cc``, downloaded datasets — lives under ONE git-ignored
directory inside the checkout, never under ``~`` or a temporary name: a
fresh checkout starts empty (so what it loads was built from the tree
as committed), and the path is stable (it is part of the compilation
cache's key — a directory that moves never hits).
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_ROOT = os.path.join(CHECKOUT, ".cache")


def cache_path(*parts: str) -> str:
    return os.path.join(CACHE_ROOT, *parts)


def jax_cache_dir() -> str:
    """The persistent compilation cache directory in effect: wherever
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside, else the
    checkout's fixed ``.cache/jax``. THE one statement of the rule —
    ``paddle_tpu/__init__`` applies it, ``chip_smoke.py`` prints it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or cache_path("jax")
