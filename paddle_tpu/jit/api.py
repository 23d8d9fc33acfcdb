"""paddle.jit parity: to_static / save / load.

Reference: python/paddle/jit/api.py:222 (`to_static`), jit.save ->
TranslatedLayer (python/paddle/jit/translated_layer.py). The reference
compiles by rewriting Python AST into a static Program executed through the
run_program op (paddle/fluid/eager/to_static/run_program_op_node.h). Here a
decorated Layer/function is traced by `jax.jit` into one XLA program:
control flow is ordinary Python at trace time, the compile cache is keyed by
input tree-structure + static values (jax.jit adds shape/dtype keying), and
the autograd tape sees the whole compiled program as ONE node — per-op
dispatch disappears, the analog of InterpreterCore's instruction list being
replaced by a fused HLO module.

jit.save/load serializes the traced program as StableHLO via jax.export —
the portable deployment artifact (role of __model__ + params in the
reference's save_inference_model).
"""
from __future__ import annotations

import functools
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..autograd import tape as _tape
from ..core.tensor import Tensor
from .functional import (EXPORT_DISABLED_CHECKS, functional_call, raw_state,
                         _wrap)

__all__ = ["to_static", "not_to_static", "ignore_module", "InputSpec",
           "save", "load", "TranslatedLayer"]


class InputSpec:
    """Parity: paddle.static.InputSpec — declared shape/dtype for tracing."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name
        self.stop_gradient = stop_gradient

    def _example(self):
        shape = [1 if (d is None or d < 0) else d for d in self.shape]
        from ..framework.dtype import convert_dtype
        return jnp.zeros(shape, dtype=convert_dtype(self.dtype))

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype!r}, name={self.name!r})"


def _is_array(x):
    return isinstance(x, (Tensor, jax.Array, np.ndarray))


def _to_raw(x):
    if isinstance(x, Tensor):
        return x.value
    if isinstance(x, np.ndarray):
        return jnp.asarray(x)
    return x


def _static_key(x):
    try:
        hash(x)
        return x
    except TypeError:
        return repr(x)


class StaticFunction:
    """A compiled callable over a Layer or plain function.

    Parity: StaticFunction (python/paddle/jit/dy2static/program_translator.py:299);
    the per-(structure, static-args) entries play the role of ConcreteProgram
    (:929), with jax.jit supplying the shape/dtype-keyed compile cache.
    """

    def __init__(self, target, input_spec=None, build_strategy=None,
                 full_graph=True, backend=None, forward_fn=None):
        from ..nn.layer_base import Layer
        self._target = target
        self._input_spec = input_spec
        self._is_layer = isinstance(target, Layer)
        self._layer = target if self._is_layer else None
        self._fn = forward_fn or (target.forward if self._is_layer else target)
        self._param_items = None
        self._buf_items = None
        self._jit_cache: Dict[Any, Callable] = {}
        # During jax tracing the Layer's (patched) forward is re-entered by
        # functional_call; this flag routes that inner call to the original
        # python forward instead of recursing into the compiler.
        self._tracing = False
        functools.update_wrapper(self, self._fn)

    # -- cache plumbing --------------------------------------------------
    def _split_args(self, args, kwargs):
        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        arrays, statics, is_dyn = [], [], []
        for leaf in leaves:
            if _is_array(leaf):
                # keep the Tensor object itself: tape.apply must see the
                # caller's Tensor so gradients flow back through compiled
                # sublayers into upstream graph nodes
                arrays.append(leaf if isinstance(leaf, Tensor)
                              else Tensor(_to_raw(leaf)))
                is_dyn.append(True)
            else:
                statics.append(leaf)
                is_dyn.append(False)
        return arrays, statics, tuple(is_dyn), treedef

    def _rebuild(self, arrays, statics, is_dyn, treedef):
        arrays, statics = list(arrays), list(statics)
        leaves = [arrays.pop(0) if d else statics.pop(0) for d in is_dyn]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    def _get_jitted(self, statics, is_dyn, treedef, n_params, n_bufs, training):
        key = (tuple(_static_key(s) for s in statics), is_dyn, treedef,
               training)
        jitted = self._jit_cache.get(key)
        if jitted is not None:
            return jitted

        layer, fn = self._layer, self._fn
        if self._is_layer:
            pnames = [n for n, _ in layer.named_parameters()]
            bnames = [n for n, _ in layer.named_buffers()]

            def pure(*flat):
                params = dict(zip(pnames, flat[:n_params]))
                bufs = dict(zip(bnames, flat[n_params:n_params + n_bufs]))
                arrays = flat[n_params + n_bufs:]
                args, kwargs = self._rebuild(arrays, statics, is_dyn, treedef)
                self._tracing = True
                try:
                    out, new_bufs = functional_call(
                        layer, params, bufs, *args, training=training,
                        **kwargs)
                finally:
                    self._tracing = False
                out_leaves, out_tree = jax.tree_util.tree_flatten(out)
                return tuple(out_leaves) + tuple(new_bufs[n] for n in bnames), \
                    out_tree
        else:
            def pure(*flat):
                args, kwargs = self._rebuild(flat, statics, is_dyn, treedef)
                with _tape.no_grad():
                    out = fn(*args, **kwargs)
                from .functional import _unwrap
                out_leaves, out_tree = jax.tree_util.tree_flatten(_unwrap(out))
                return tuple(out_leaves), out_tree

        out_tree_box = {}

        @jax.jit
        def jitted(*flat):
            leaves, out_tree = pure(*flat)
            out_tree_box["tree"] = out_tree
            return leaves

        jitted._out_tree_box = out_tree_box
        self._jit_cache[key] = jitted
        return jitted

    # -- call ------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if self._tracing:
            return self._fn(*args, **kwargs)
        if not _to_static_enabled:
            # enable_to_static(False): run the original eager function
            return self._fn(*args, **kwargs)
        arrays, statics, is_dyn, treedef = self._split_args(args, kwargs)
        if self._is_layer:
            layer = self._layer
            training = layer.training
            if self._param_items is None:
                self._param_items = list(layer.named_parameters())
                self._buf_items = list(layer.named_buffers())
            param_items, buf_items = self._param_items, self._buf_items
            jitted = self._get_jitted(statics, is_dyn, treedef,
                                      len(param_items), len(buf_items),
                                      training)
            n_bufs = len(buf_items)
            param_tensors = [p for _, p in param_items]
            flat_in = param_tensors + [b for _, b in buf_items] + arrays
            outs = _apply_traced(jitted, flat_in)
            out_tree = jitted._out_tree_box["tree"]
            if n_bufs:
                out_leaves, buf_outs = outs[:len(outs) - n_bufs], outs[-n_bufs:]
                with _tape.no_grad():
                    for (name, b), new in zip(buf_items, buf_outs):
                        b.value = new.value
            else:
                out_leaves = outs
            out = jax.tree_util.tree_unflatten(out_tree, list(out_leaves))
            return _retree_tensors(out)
        else:
            jitted = self._get_jitted(statics, is_dyn, treedef, 0, 0, None)
            outs = _apply_traced(jitted, arrays)
            out_tree = jitted._out_tree_box["tree"]
            out = jax.tree_util.tree_unflatten(out_tree, list(outs))
            return _retree_tensors(out)

    # descriptor protocol so @to_static on Layer.forward compiles per
    # instance (params are traced arguments, never baked-in constants)
    def __get__(self, instance, owner):
        if instance is None:
            return self
        from ..nn.layer_base import Layer
        if not isinstance(instance, Layer):
            return functools.partial(self.__call__, instance)
        bound = instance.__dict__.get("__static_forward__")
        if bound is None:
            bound = StaticFunction(instance, self._input_spec,
                                   forward_fn=self._fn.__get__(instance, owner))
            object.__setattr__(instance, "__static_forward__", bound)
        return bound

    @property
    def concrete_programs(self):
        return list(self._jit_cache)


# tree re-wrap shares functional._wrap (Tensor leaves pass through)
_retree_tensors = _wrap


def _apply_traced(jitted, flat_in):
    """Run the jitted program through the tape, translating jax's
    data-dependent-control-flow tracing errors into guidance naming the
    combinators (the role of the reference's dy2static transformer error
    messages, python/paddle/jit/dy2static/error.py)."""
    try:
        return _tape.apply(lambda *f: tuple(jitted(*f)), *flat_in,
                           _op_name="jit_program")
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError) as e:
        kind = ("a Python `if`/`while` condition" if isinstance(
            e, jax.errors.TracerBoolConversionError) else "a Python value")
        raise RuntimeError(
            "to_static: the traced function used a Tensor whose value is "
            f"only known at run time as {kind}. A traced XLA program "
            "cannot branch on data in Python — use the in-program "
            "control-flow combinators instead: paddle.static.nn.cond / "
            "while_loop / case / switch_case (they lower to lax.cond / "
            "lax.while_loop / lax.switch). Reference parity: "
            "python/paddle/static/nn/control_flow.py."
        ) from e


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Compile a Layer or function into one XLA program.

    Parity: paddle.jit.to_static (python/paddle/jit/api.py:222)."""
    def decorate(target):
        from ..nn.layer_base import Layer
        if isinstance(target, Layer):
            static = StaticFunction(target, input_spec, build_strategy)
            target.forward = static
            target._static_function = static
            return target
        return StaticFunction(target, input_spec, build_strategy)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    """Parity marker: paddle.jit.not_to_static — tracing runs the plain
    Python anyway, so this is the identity."""
    return fn


def ignore_module(modules):
    return None


# ---------------------------------------------------------------------------
# save / load: StableHLO program + params (deployment artifact)
# ---------------------------------------------------------------------------

def save(layer, path, input_spec=None, platforms=None, **config):
    """Serialize `layer` (or decorated StaticFunction) for serving.

    Writes `<path>.pdmodel` (StableHLO bytes via jax.export) and
    `<path>.pdiparams` (pickled numpy state). Parity: paddle.jit.save
    (python/paddle/jit/api.py) producing __model__ + params.

    `platforms`: jax.export lowering targets. Default: when saving on a
    CPU host the artifact is lowered for BOTH ("cpu", "tpu") so a model
    exported on a dev machine serves on the TPU fleet (the reference's
    __model__ is backend-portable the same way); when saving on a TPU
    the trace may contain Mosaic kernels, so it stays TPU-only.
    """
    from ..nn.layer_base import Layer
    if isinstance(layer, StaticFunction):
        # @to_static-decorated: unwrap to the Layer or plain function,
        # inheriting the decoration-time input_spec when save's is None
        if input_spec is None:
            input_spec = layer._input_spec
        layer = layer._layer if layer._is_layer else layer._fn
    if not isinstance(layer, Layer) and not callable(layer):
        raise TypeError("jit.save expects a Layer or a function")
    if input_spec is None:
        raise ValueError("jit.save requires input_spec (shape/dtype of inputs)")
    from ..framework.dtype import convert_dtype
    examples = []
    n_sym = 0
    # one scope so dynamic dims of different inputs can co-exist in one program
    sym_scope = jax.export.SymbolicScope()
    for spec in input_spec:
        if isinstance(spec, InputSpec):
            # None/-1 dims export as symbolic dims so the serialized
            # program serves any batch size (reference dynamic-shape parity)
            dims, has_sym = [], False
            for d in spec.shape:
                if d is None or d < 0:
                    dims.append(f"_dyn{n_sym}")
                    n_sym += 1
                    has_sym = True
                else:
                    dims.append(str(d))
            if has_sym:
                shape = jax.export.symbolic_shape(",".join(dims),
                                                  scope=sym_scope)
            else:
                shape = tuple(int(d) for d in dims)
            examples.append(jax.ShapeDtypeStruct(
                shape, convert_dtype(spec.dtype)))
        elif isinstance(spec, Tensor):
            examples.append(spec.value)
        else:
            examples.append(jnp.asarray(spec))

    is_layer = isinstance(layer, Layer)
    if is_layer:
        params, buffers = raw_state(layer)
        was_training = layer.training
        layer.eval()
    else:
        # plain function: no state; the program closes over nothing
        params, buffers, was_training = {}, {}, False
    pnames, bnames = list(params), list(buffers)
    try:
        if is_layer:
            def infer(params_and_bufs, *args):
                p = {n: params_and_bufs[n] for n in pnames}
                b = {n: params_and_bufs[n] for n in bnames}
                out, _ = functional_call(layer, p, b, *args,
                                         training=False)
                return out
        else:
            def infer(params_and_bufs, *args):
                from .functional import _unwrap
                with _tape.no_grad():
                    out = layer(*[_wrap(a) for a in args])
                return _unwrap(out)

        merged = {**params, **buffers}
        if isinstance(platforms, str):
            platforms = (platforms,)
        elif platforms is not None:
            platforms = tuple(platforms)
            if not platforms:
                raise ValueError(
                    "jit.save: platforms must be None or a non-empty "
                    "sequence of platform names ('cpu', 'tpu')")
        defaulted = platforms is None and jax.default_backend() == "cpu"
        if defaulted:
            platforms = ("cpu", "tpu")

        def _export(plats):
            return jax.export.export(
                jax.jit(infer), disabled_checks=EXPORT_DISABLED_CHECKS,
                **({"platforms": plats} if plats else {}),
            )(merged, *examples)

        try:
            exported = _export(platforms)
        except Exception:
            if not defaulted:
                raise
            # the dual-platform default must not break models that only
            # lower for the native backend — fall back with a warning
            import warnings
            warnings.warn(
                "jit.save: TPU cross-lowering failed; artifact exported "
                "for 'cpu' only (pass platforms=(...,) to control this)")
            exported = _export(("cpu",))
    finally:
        if was_training:
            layer.train()

    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        f.write(exported.serialize())
    state = {n: np.asarray(v) for n, v in merged.items()}
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({"state": state,
                     "input_spec": [(list(str(d) for d in e.shape),
                                     str(e.dtype)) for e in examples]}, f)


class TranslatedLayer:
    """A loaded serving program. Parity: TranslatedLayer
    (python/paddle/jit/translated_layer.py) — call it like a Layer."""

    def __init__(self, exported, state):
        self._exported = exported
        self._state = {n: jnp.asarray(v) for n, v in state.items()}
        self.training = False

    def __call__(self, *args):
        raw = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
               for a in args]
        out = self._exported.call(self._state, *raw)
        return _wrap(out)

    forward = __call__

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is an inference program")


def load(path, **config) -> TranslatedLayer:
    """Parity: paddle.jit.load."""
    with open(path + ".pdmodel", "rb") as f:
        exported = jax.export.deserialize(f.read())
    with open(path + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    return TranslatedLayer(exported, meta["state"])


_to_static_enabled = True


def enable_to_static(enable: bool = True):
    """Parity: jit/api.py enable_to_static — globally toggle whether
    @to_static functions actually compile (False = run eagerly)."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


def set_verbosity(level: int = 0, also_to_stdout: bool = False):
    """Parity: jit dy2static logging verbosity (trace-based compilation
    here has one log channel)."""
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level else logging.WARNING)


def set_code_level(level: int = 100, also_to_stdout: bool = False):
    """Parity: jit set_code_level — the reference dumps transformed AST
    code; trace-based jit has no rewritten source, so this toggles HLO
    text logging instead."""
    import logging
    logging.getLogger("paddle_tpu.jit.hlo").setLevel(
        logging.DEBUG if level else logging.WARNING)
