"""Layer: the module system.

Parity: paddle.nn.Layer (python/paddle/fluid/dygraph/layers.py) — named
parameters/sublayers/buffers, state_dict, train/eval, hooks, create_parameter
with ParamAttr + initializer. TPU-first addition: `raw_state()` /
`functional_call()` (in ..jit.functional) flatten a Layer into a params
pytree so the whole model becomes a pure function for jax.jit/pjit — the
reference needs dy2static AST rewriting (python/paddle/jit/dy2static/) for
this; tracing needs nothing.
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, Optional, Tuple

import numpy as np
from jax import named_scope as _named_scope

from ..core.tensor import Parameter, Tensor
from ..framework.dtype import convert_dtype
from . import initializer as I


class ParamAttr:
    """Parity: paddle.ParamAttr."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None or attr is True:
            return ParamAttr()
        if attr is False:
            return None
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"Invalid param attr: {attr!r}")


class Layer:
    # names of this layer's own buffers that keep their type under
    # astype() / bfloat16(): state that is no activation (counters, a
    # routing bias stepped by thousandths), which a cast would coarsen
    _fixed_dtype_buffers = frozenset()

    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", collections.OrderedDict())
        object.__setattr__(self, "_sub_layers", collections.OrderedDict())
        object.__setattr__(self, "_buffers", collections.OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtype
        self._name_scope = name_scope or self.__class__.__name__.lower()
        # what `__call__` names this layer's operations in a traced
        # program: the key its parent registers it under, until then
        # (a root) the class name. No id or counter: the name is part of
        # a compiled program's cache key
        self._scope_name = self.__class__.__name__.lower()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._hook_id = 0

    # ---- attribute routing ----
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        subs = self.__dict__.get("_sub_layers")
        bufs = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            for d in (subs, bufs):
                if d is not None and name in d:
                    del d[name]
            params[name] = value
        elif isinstance(value, Layer):
            for d in (params, bufs):
                if d is not None and name in d:
                    del d[name]
            subs[name] = value
            value._scope_name = name
        elif bufs is not None and name in bufs:
            # re-assigning an existing buffer keeps it registered
            if isinstance(value, Tensor):
                bufs[name] = value
            else:
                del bufs[name]
                object.__setattr__(self, name, value)
        elif params is not None and name in params:
            if value is None:
                del params[name]
            else:
                raise TypeError(
                    f"cannot assign non-Parameter to parameter {name!r}")
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for d in ("_parameters", "_sub_layers", "_buffers"):
            dd = self.__dict__.get(d)
            if dd is not None and name in dd:
                return dd[name]
        raise AttributeError(
            f"{self.__class__.__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for d in ("_parameters", "_sub_layers", "_buffers"):
            dd = self.__dict__.get(d)
            if dd is not None and name in dd:
                del dd[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._sub_layers) + list(self._buffers)

    # ---- construction helpers ----
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """Parity: Layer.create_parameter (dygraph/layers.py) via LayerHelper."""
        attr = ParamAttr._to_attr(attr)
        if attr is None:
            return None
        dtype = dtype or self._dtype
        # precedence (reference set_global_initializer contract): an
        # initializer in ParamAttr wins; otherwise a registered global
        # default overrides the layer's built-in default
        init = attr.initializer or I._global_initializer(is_bias) or \
            default_initializer or \
            (I.Constant(0.0) if is_bias else I.XavierNormal())
        value = init(shape, dtype)
        p = Parameter(value, trainable=attr.trainable, name=attr.name)
        p.optimize_attr["learning_rate"] = attr.learning_rate
        p.regularizer = attr.regularizer
        p.need_clip = attr.need_clip
        return p

    def add_parameter(self, name, parameter):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if sublayer is not None:
            sublayer._scope_name = str(name)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ---- traversal ----
    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        layers_set = layers_set if layers_set is not None else set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None or id(sub) in layers_set:
                continue
            layers_set.add(id(sub))
            p = prefix + ("." if prefix else "") + name
            yield p, sub
            yield from sub.named_sublayers(prefix=p, include_self=False,
                                           layers_set=layers_set)

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is None or id(p) in seen:
                continue
            seen.add(id(p))
            yield prefix + ("." if prefix else "") + name, p
        if include_sublayers:
            for lname, sub in self.named_sublayers(prefix=prefix):
                for name, p in sub._parameters.items():
                    if p is None or id(p) in seen:
                        continue
                    seen.add(id(p))
                    yield lname + "." + name, p

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield prefix + ("." if prefix else "") + name, b
        if include_sublayers:
            for lname, sub in self.named_sublayers(prefix=prefix):
                for name, b in sub._buffers.items():
                    if b is not None:
                        yield lname + "." + name, b

    # ---- mode ----
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            self._cast_all(convert_dtype(dtype))
        return self

    def astype(self, dtype):
        self._cast_all(convert_dtype(dtype))
        return self

    def _cast_all(self, dt, float_only=True):
        import jax as _jax

        from ..framework.dtype import is_inexact

        def cast(v):
            if isinstance(v, _jax.ShapeDtypeStruct):  # abstract (LazyGuard)
                return _jax.ShapeDtypeStruct(v.shape, dt)
            return v.astype(dt)

        for p in self.parameters():
            if not float_only or is_inexact(p.value.dtype):
                p.value = cast(p.value)
        for layer in [self] + self.sublayers():
            for name, b in layer._buffers.items():
                if b is None or name in layer._fixed_dtype_buffers:
                    continue
                if not float_only or is_inexact(b.value.dtype):
                    b.value = cast(b.value)

    def float(self):
        return self.astype("float32")

    def bfloat16(self):
        return self.astype("bfloat16")

    def half(self):
        return self.astype("float16")

    # ---- state dict ----
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix,
                                             include_sublayers=include_sublayers):
            dest[name] = p
        skip = set()
        for lname, sub in [("", self)] + list(self.named_sublayers()):
            for bname in sub._non_persistable_buffer_names:
                skip.add((lname + "." if lname else "") + bname)
        for name, b in self.named_buffers(prefix=structured_name_prefix,
                                          include_sublayers=include_sublayers):
            if name not in skip:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v.value if isinstance(v, Tensor) else np.asarray(v)
                t.set_value(arr)
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ---- hooks ----
    def register_forward_pre_hook(self, hook):
        hid = self._hook_id
        self._hook_id += 1
        self._forward_pre_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = self._hook_id
        self._hook_id += 1
        self._forward_post_hooks[hid] = hook
        return _HookRemoveHelper(self._forward_post_hooks, hid)

    # ---- call ----
    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        with _named_scope(self._scope_name):
            outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            body = repr(sub).split("\n")
            head = f"({name}): {body[0]}"
            lines.append(head)
            lines.extend("  " + b for b in body[1:])
        main = self.__class__.__name__ + "(" + extra
        if lines:
            return main + "\n  " + "\n  ".join(lines) + "\n)"
        return main + ")"

    def full_name(self):
        return self._name_scope

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


class _HookRemoveHelper:
    def __init__(self, hooks, hid):
        self._hooks = hooks
        self._hid = hid

    def remove(self):
        self._hooks.pop(self._hid, None)
