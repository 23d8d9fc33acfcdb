"""Layer <-> pure-function bridge.

This is the TPU-native replacement for the reference's entire dy2static
subsystem (python/paddle/jit/dy2static/ — 20 AST transformer files,
ProgramTranslator, PartialProgramLayer): instead of rewriting Python source
into a static Program, we flatten a Layer into a params/buffers pytree and
re-enter its ordinary Python `forward` under JAX tracing. No AST rewriting,
no scope cache, no run_program op — `jax.jit` caches by abstract shapes.

`raw_state(layer)` -> (params, buffers) pytrees of raw jax arrays.
`functional_call(layer, params, buffers, *args)` -> (outputs, new_buffers):
runs forward with the given arrays swapped into the Layer, capturing buffer
mutations (e.g. BatchNorm running stats) as returned state — the functional
idiom XLA needs for donation and sharding.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Tuple

import jax

from ..autograd.tape import no_grad
from ..core.tensor import Tensor


# jax.export refuses a custom call whose target carries no compatibility
# guarantee. The one this package's own layers emit is an identity that
# pins a layout and computes nothing
# (jax.experimental.layout.with_layout_constraint, models/gpt.py), so
# every export of a traced program (jit.save, ParallelTrainStep's
# aot_compile) admits that target and no other
EXPORT_DISABLED_CHECKS = (
    jax.export.DisabledSafetyCheck.custom_call("LayoutConstraint"),)


def raw_state(layer) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Flatten a Layer's parameters and persistable+non-persistable buffers
    into two name->jax.Array dicts (pytrees)."""
    params = {n: p.value for n, p in layer.named_parameters()}
    buffers = {n: b.value for n, b in layer.named_buffers()}
    return params, buffers


def load_state(layer, params: Dict[str, Any], buffers: Dict[str, Any] = None):
    """Write raw arrays back into the Layer's tensors (inverse of raw_state)."""
    pmap = dict(layer.named_parameters())
    for n, v in params.items():
        pmap[n].value = v
    if buffers:
        bmap = dict(layer.named_buffers())
        for n, v in buffers.items():
            if n in bmap:
                bmap[n].value = v
    return layer


@contextlib.contextmanager
def _swapped_state(layer, params, buffers):
    """Temporarily rebind the Layer's tensor payloads to the given arrays
    (which may be tracers), restoring originals on exit."""
    pmap = dict(layer.named_parameters())
    bmap = dict(layer.named_buffers())
    saved = {}
    try:
        for n, v in params.items():
            saved[id(pmap[n])] = (pmap[n], pmap[n].value)
            pmap[n].value = v
        for n, v in (buffers or {}).items():
            if n in bmap:
                saved[id(bmap[n])] = (bmap[n], bmap[n].value)
                bmap[n].value = v
        yield pmap, bmap
    finally:
        for t, old in saved.values():
            t.value = old


def functional_call(layer, params, buffers, *args, training=None, **kwargs):
    """Run `layer(*args, **kwargs)` as a pure function of (params, buffers).

    Tensor/array args are accepted interchangeably; returns
    (outputs_as_raw_arrays, new_buffers). Autograd taping is disabled —
    differentiation of the pure function is `jax.grad`'s job.
    """
    args = tuple(Tensor(a) if isinstance(a, jax.Array) else a for a in args)
    kwargs = {k: Tensor(v) if isinstance(v, jax.Array) else v
              for k, v in kwargs.items()}
    prev_training = layer.training
    if training is not None:
        layer.train() if training else layer.eval()
    try:
        with _swapped_state(layer, params, buffers) as (_, bmap):
            with no_grad():
                out = layer(*args, **kwargs)
            new_buffers = {n: bmap[n].value for n in (buffers or {})
                           if n in bmap}
            # unwrap INSIDE the swap: a forward that returns a Parameter
            # or buffer Tensor (e.g. a tied LM weight handed to a fused
            # loss) must yield the traced value — after the swap restores
            # originals, .value would silently be the stale concrete
            # array, freezing that leaf in the compiled program
            out = _unwrap(out)
    finally:
        if training is not None:
            layer.train() if prev_training else layer.eval()
    return out, new_buffers


def _unwrap(out):
    if isinstance(out, Tensor):
        return out.value
    if isinstance(out, (tuple, list)):
        return type(out)(_unwrap(o) for o in out)
    if isinstance(out, dict):
        return {k: _unwrap(v) for k, v in out.items()}
    return out


def _wrap(out, stop_gradient=True):
    if isinstance(out, jax.Array):
        return Tensor(out, stop_gradient=stop_gradient)
    if isinstance(out, (tuple, list)):
        return type(out)(_wrap(o, stop_gradient) for o in out)
    if isinstance(out, dict):
        return {k: _wrap(v, stop_gradient) for k, v in out.items()}
    return out
