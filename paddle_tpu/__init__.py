"""paddle_tpu — a TPU-native deep learning framework.

Brand-new JAX/XLA/Pallas/pjit architecture with the capabilities of the
reference (PaddlePaddle ~2.5-dev at /root/reference): eager define-by-run
tensors + autograd, jit trace-to-XLA, hybrid-parallel training over device
meshes, AMP, recompute, sharded checkpointing, profiling, and a serving path.
See SURVEY.md for the layer-by-layer mapping.
"""
from __future__ import annotations

import os as _os

# Persistent XLA compilation cache (reference pays per-op dispatch at runtime;
# we pay XLA compiles — amortize them across runs; SURVEY.md §7 hard parts).
import jax as _jax

# Multi-host formation must precede ANY backend touch (jax.devices etc.),
# so when the launcher declared a multi-process world via the JAX_* env
# contract, form it now — before the imports below initialize XLA.
from ._bootstrap import maybe_init_jax_distributed as _mijd

_mijd()

from .framework import flags as _flags

# XLA:CPU AOT artifacts are machine-feature sensitive: reloading one in a
# process whose feature probe differs (different host, or multi-device CPU
# programs compiled with prefer-no-scatter/gather pseudo-features that
# never appear in the host probe) logs "could lead to SIGILL"
# (cpu_aot_loader) and genuinely can crash across hosts. CPU compiles are
# fast; the cache's value is the TPU's minutes-long compiles — so the
# persistent cache is skipped only when the platform explicitly names
# cpu. Unset JAX_PLATFORMS keeps the cache: that is the normal TPU
# deployment (jax auto-detects the chip), exactly the case the cache
# exists to amortize.
#
# Placement (one rule, _paths.jax_cache_dir): where
# JAX_COMPILATION_CACHE_DIR is set the program sets NO directory in code
# — jax reads the variable itself, so a launcher can place the cache
# from outside; otherwise it is the checkout's fixed .cache/jax (the
# path is part of the cache key: never ~, a temporary name, a pid or a
# time).
_plat = _os.environ.get("JAX_PLATFORMS", "").lower()
if _flags.flag_value("use_persistent_compilation_cache") and \
        "cpu" not in _plat:
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _cache_dir = _flags.flag_value("compilation_cache_dir")
        _os.makedirs(_cache_dir, exist_ok=True)
        _jax.config.update("jax_compilation_cache_dir", _cache_dir)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # A Pallas kernel's Mosaic payload is serialized WITH its MLIR
    # locations, and jax's cache-key canonicalization cannot strip what
    # sits inside a custom call's backend_config. With full Python
    # tracebacks in those locations (jax's default, ten frames), moving
    # a line in ANY caller of a kernel — a model file, a driver script —
    # re-keys every program that contains one: a 327 s train-step
    # compile missed a warm cache for exactly that reason (PERF.md
    # section 6, PR 24/27). Innermost frame only keeps the key to
    # the kernel's own source.
    _jax.config.update("jax_include_full_tracebacks_in_locations", False)

from .core.tensor import Tensor, Parameter  # noqa: F401,E402
from .core.tensor_types import (  # noqa: F401,E402
    TensorArray, SelectedRows, StringTensor, create_array, array_write,
    array_read, array_length)
from .tensor import *  # noqa: F401,F403,E402  (creation/math/... API)
from .tensor import to_tensor  # noqa: F401,E402
from .framework import seed, set_flags, get_flags  # noqa: F401,E402
from .framework.lazy_init import LazyGuard  # noqa: F401,E402
from .framework import get_rng_state, set_rng_state  # noqa: F401,E402
# cuda-named aliases (reference exposes them top-level; one RNG here)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state
from .framework.dtype import dtype  # noqa: E402  (paddle.dtype parity)
from .framework.dtype import (  # noqa: F401,E402
    bool, uint8, int8, int16, int32, int64, float16, bfloat16, float32,
    float64, complex64, complex128)
from .autograd import no_grad, enable_grad, set_grad_enabled, grad  # noqa: F401,E402
from .autograd import is_grad_enabled  # noqa: F401,E402

from . import autograd  # noqa: F401,E402
from . import cost_model  # noqa: F401,E402
from . import device  # noqa: F401,E402
from . import framework  # noqa: F401,E402
from . import nn  # noqa: F401,E402
from .nn import ParamAttr  # noqa: E402  (paddle.ParamAttr parity)
from . import optimizer  # noqa: F401,E402
from . import amp  # noqa: F401,E402
from . import jit  # noqa: F401,E402
from . import distributed  # noqa: F401,E402
from . import io as _io_mod  # noqa: F401,E402
from .io import save, load  # noqa: F401,E402
from .device import (  # noqa: F401,E402
    set_device, get_device, is_compiled_with_cuda, is_compiled_with_tpu)
from .distributed.parallel import DataParallel  # noqa: E402  (paddle.DataParallel parity)
from . import metric  # noqa: E402
from . import vision  # noqa: E402
from . import quantization  # noqa: E402
from . import geometric  # noqa: E402
from . import text  # noqa: E402
from . import audio  # noqa: E402
from . import signal  # noqa: E402
from . import fft  # noqa: E402
from . import reader  # noqa: E402
from . import regularizer  # noqa: E402
from . import sysconfig  # noqa: E402
from . import hub  # noqa: E402
from . import onnx  # noqa: E402
from . import dataset  # noqa: E402
from . import version  # noqa: E402
from . import incubate  # noqa: E402
from . import utils  # noqa: E402
from .framework import custom_op  # noqa: E402
from .framework.custom_op import ops  # noqa: E402  (custom-op namespace)
from . import models  # noqa: E402
from . import hapi  # noqa: E402
from . import profiler  # noqa: E402
from . import inference  # noqa: E402
from . import static  # noqa: E402
from . import distribution  # noqa: E402
from . import sparse  # noqa: E402
from .hapi import Model  # noqa: E402  (paddle.Model parity)
from .hapi import callbacks  # noqa: E402  (paddle.callbacks parity)


def summary(net, input_size=None, dtypes=None, input=None):
    """Parity: paddle.summary (hapi/model_summary.py:29) — returns
    {'total_params', 'trainable_params'}. input_size/dtypes/input are
    accepted for API parity; parameter counting needs neither since
    layers are eagerly materialized."""
    from .hapi import Model as _M
    return _M(net).summary(input_size=input_size)

# default dtype management (paddle.set_default_dtype)
_default_dtype = "float32"


def set_default_dtype(d):
    global _default_dtype
    from .framework.dtype import convert_dtype
    _default_dtype = str(convert_dtype(d))


def get_default_dtype():
    return _default_dtype


def in_dynamic_mode():
    """Parity: paddle.in_dynamic_mode — eager unless inside a jit trace."""
    import jax.core as jcore
    try:
        return not isinstance(jcore.get_aval(0), jcore.Tracer)
    except Exception:
        return True


disable_static = lambda: None  # noqa: E731 — eager is the only mode
enable_static = lambda: None  # noqa: E731

__version__ = version.full_version
