"""C++ extension loader — out-of-tree native ops.

Parity: python/paddle/utils/cpp_extension/ (load/CppExtension) and the
phi C-ABI (paddle/phi/capi/capi.h). The reference JIT-compiles a
custom-op .so against paddle/extension.h; here the contract is a plain
C ABI (no framework headers needed) and the compiled function runs
host-side, bridged into traced programs with jax.pure_callback — the
right TPU split: device kernels belong in Pallas (framework/custom_op),
C++ belongs on the host (IO, CPU pre/post-processing, legacy numerics).

C ABI (float32):

    extern "C" void <op>(const float* const* ins,
                         const long long* const* shapes,
                         const int* ndims, int n_ins, float* out);

    // optional gradient: last input is the output cotangent, writes one
    // grad buffer per ORIGINAL input
    extern "C" void <op>_grad(const float* const* ins,
                              const long long* const* shapes,
                              const int* ndims, int n_ins,
                              float* const* grad_outs);
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Callable, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .. import _paths
from ..autograd.tape import apply

__all__ = ["load", "CppExtension", "get_build_directory"]

_ARGTYPES = [
    ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
    ctypes.POINTER(ctypes.POINTER(ctypes.c_longlong)),
    ctypes.POINTER(ctypes.c_int),
    ctypes.c_int,
]


def get_build_directory(override: Optional[str] = None) -> str:
    d = (override or os.environ.get("PADDLE_EXTENSION_DIR")
         or _paths.cache_path("extensions"))
    os.makedirs(d, exist_ok=True)
    return d


def _compile(name: str, sources: Sequence[str], extra_cxx_flags,
             build_directory: Optional[str] = None) -> str:
    tag = hashlib.sha1()
    for src in sources:
        with open(src, "rb") as f:
            tag.update(f.read())
    tag.update(" ".join(extra_cxx_flags or []).encode())
    out = os.path.join(get_build_directory(build_directory),
                       f"lib{name}_{tag.hexdigest()[:12]}.so")
    if not os.path.exists(out):
        # build to a temp name and rename: a killed/concurrent build must
        # never leave a truncated .so behind the cache check
        tmp = out + f".tmp{os.getpid()}"
        cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
               *(extra_cxx_flags or []), *sources, "-o", tmp]
        proc = subprocess.run(cmd, capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"cpp_extension build failed:\n{' '.join(cmd)}\n"
                f"{proc.stderr}")
        os.replace(tmp, out)
    return out


def _marshal(arrays):
    arrs = [np.ascontiguousarray(np.asarray(a, np.float32))
            for a in arrays]
    ins = (ctypes.POINTER(ctypes.c_float) * len(arrs))(*[
        a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for a in arrs])
    shape_bufs = [np.asarray(a.shape, np.longlong) for a in arrs]
    shapes = (ctypes.POINTER(ctypes.c_longlong) * len(arrs))(*[
        s.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))
        for s in shape_bufs])
    ndims = (ctypes.c_int * len(arrs))(*[a.ndim for a in arrs])
    return arrs, shape_bufs, ins, shapes, ndims


class CppExtension:
    """A loaded extension library. `call` runs an exported op as a
    framework op (eager and under jit via pure_callback); gradients use
    the `<op>_grad` export when present."""

    def __init__(self, name: str, lib_path: str):
        self.name = name
        self._path = lib_path
        self._lib = ctypes.CDLL(lib_path)

    def _fn(self, op_name, grad=False):
        try:
            fn = getattr(self._lib, op_name + ("_grad" if grad else ""))
        except AttributeError:
            return None
        if grad:
            fn.argtypes = _ARGTYPES + [
                ctypes.POINTER(ctypes.POINTER(ctypes.c_float))]
        else:
            fn.argtypes = _ARGTYPES + [ctypes.POINTER(ctypes.c_float)]
        fn.restype = None
        return fn

    def call(self, op_name: str, *tensors, out_shape=None,
             out_dtype=jnp.float32):
        """Run `op_name` on the inputs; out_shape defaults to the first
        input's shape (elementwise convention)."""
        fwd = self._fn(op_name)
        if fwd is None:
            raise AttributeError(
                f"{self._path} exports no symbol {op_name!r}")
        grad_fn = self._fn(op_name, grad=True)

        def host_fwd(*arrays):
            arrs, _sb, ins, shapes, ndims = _marshal(arrays)
            shape = tuple(out_shape) if out_shape is not None \
                else arrs[0].shape
            out = np.zeros(shape, np.float32)
            fwd(ins, shapes, ndims, len(arrs),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
            # the C ABI is float32; honor the promised callback dtype
            return out.astype(np.dtype(out_dtype), copy=False)

        def host_bwd(*arrays_and_ct):
            arrs, _sb, ins, shapes, ndims = _marshal(arrays_and_ct)
            n_orig = len(arrs) - 1
            grads = [np.zeros(a.shape, np.float32)
                     for a in arrs[:n_orig]]
            gptrs = (ctypes.POINTER(ctypes.c_float) * n_orig)(*[
                g.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                for g in grads])
            grad_fn(ins, shapes, ndims, len(arrs), gptrs)
            return tuple(grads)

        def make_callback(*xs):
            shape = tuple(out_shape) if out_shape is not None \
                else xs[0].shape
            spec = jax.ShapeDtypeStruct(shape, out_dtype)
            return jax.pure_callback(host_fwd, spec, *xs, vmap_method=None)

        if grad_fn is not None:
            core = jax.custom_vjp(make_callback)

            def fwd_rule(*xs):
                return make_callback(*xs), xs

            def bwd_rule(res, ct):
                specs = tuple(jax.ShapeDtypeStruct(x.shape, x.dtype)
                              for x in res)
                return jax.pure_callback(host_bwd, specs, *res, ct,
                                         vmap_method=None)

            core.defvjp(fwd_rule, bwd_rule)
        else:
            core = make_callback

        return apply(core, *tensors, _op_name=f"{self.name}.{op_name}")

    def __getattr__(self, op_name):
        if op_name.startswith("_"):
            raise AttributeError(op_name)

        def bound(*tensors, **kw):
            return self.call(op_name, *tensors, **kw)

        return bound


def load(name: str, sources: Sequence[str], extra_cxx_flags=None,
         extra_include_paths: Optional[Sequence[str]] = None,
         build_directory: Optional[str] = None, verbose: bool = False):
    """Parity: utils/cpp_extension.load — JIT-compile C++ sources and
    return the loaded extension."""
    flags = list(extra_cxx_flags or [])
    for inc in extra_include_paths or []:
        flags.append(f"-I{inc}")
    lib = _compile(name, sources, flags, build_directory)
    if verbose:
        print(f"[cpp_extension] {name} -> {lib}")
    return CppExtension(name, lib)


def CUDAExtension(sources, *args, **kwargs):
    """Parity: utils.cpp_extension.CUDAExtension — no CUDA toolchain in a
    TPU build; .cu sources cannot compile here."""
    raise NotImplementedError(
        "CUDAExtension requires nvcc; this is a TPU build — write the op "
        "as a jnp/pallas composition (framework.custom_op) or build a CPU "
        "C++ op with CppExtension")


def setup(name=None, ext_modules=None, **kwargs):
    """Parity: utils.cpp_extension.setup — the setuptools ceremony
    collapses onto `load()`. Accepts the ported patterns: an already-
    loaded CppExtension, a {"sources": [...]} mapping, or anything with a
    `.sources` attribute (the reference's Extension objects)."""
    exts = ext_modules if isinstance(ext_modules, (list, tuple)) else \
        ([ext_modules] if ext_modules is not None else [])
    built = []
    for i, ext in enumerate(exts):
        if isinstance(ext, CppExtension):
            built.append(ext)
            continue
        sources = (ext.get("sources") if isinstance(ext, dict)
                   else getattr(ext, "sources", None))
        if not sources:
            raise TypeError(
                "setup() expects CppExtension instances or objects with "
                f"a 'sources' list, got {type(ext)}")
        ext_name = (ext.get("name") if isinstance(ext, dict)
                    else getattr(ext, "name", None)) or name or f"ext{i}"
        built.append(load(ext_name, sources))
    return built


__all__ += ["CUDAExtension", "setup"]
