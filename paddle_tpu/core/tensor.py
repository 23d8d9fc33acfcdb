"""Eager Tensor over jax.Array.

Reference parity: the eager Tensor bound in paddle/fluid/pybind/eager.cc with
methods from eager_method.cc and math-op-patch (eager_math_op_patch.cc), plus
autograd meta (grad, stop_gradient) from paddle/fluid/eager/. TPU-first: the
payload is a jax.Array living in HBM via PJRT; all math dispatches through
the autograd tape (`..autograd.tape.apply`) to jnp/lax ops that XLA compiles.
Paddle semantics kept: tensors default to stop_gradient=True; Parameters
default to stop_gradient=False.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtypes
from ..framework.dtype import convert_dtype


def _resolve_device(spec: str):
    """Map a place string ("cpu", "tpu", "tpu:1", "gpu:0") to a jax Device,
    or None when the string is not a device spec. Unknown indices raise."""
    name, _, idx = spec.partition(":")
    name = name.lower()
    if name not in ("cpu", "tpu", "gpu", "xpu"):
        return None
    plat = "cpu" if name == "cpu" else "tpu"   # gpu/xpu alias the TPU
    try:
        devs = jax.devices(plat)
    except RuntimeError as e:
        raise ValueError(
            f"no devices available for place '{spec}'") from e
    i = int(idx) if idx else 0
    if i >= len(devs):
        raise ValueError(
            f"device index {i} out of range for '{plat}' "
            f"({len(devs)} devices)")
    return devs[i]


class Tensor:
    __slots__ = ("value", "stop_gradient", "name", "_grad", "_node",
                 "_out_index", "_retain_grads", "persistable", "__weakref__")

    _next_id = 0

    def __init__(self, value, stop_gradient=True, name=None):
        if isinstance(value, Tensor):
            value = value.value
        if not isinstance(value, (jax.Array, jax.ShapeDtypeStruct)):
            # ShapeDtypeStruct: abstract parameter under LazyGuard
            # (framework/lazy_init.py) — holds shape/dtype only
            value = jnp.asarray(value)
        self.value = value
        self.stop_gradient = stop_gradient
        if name is None:
            name = f"generated_tensor_{Tensor._next_id}"
            Tensor._next_id += 1
        self.name = name
        self._grad = None
        self._node = None
        self._out_index = 0
        self._retain_grads = False
        self.persistable = False

    # ---- basic attributes ----
    @property
    def shape(self):
        return list(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def ndim(self):
        return self.value.ndim

    dim = ndim

    @property
    def size(self):
        return int(self.value.size)

    @property
    def place(self):
        devs = getattr(self.value, "devices", None)
        try:
            return next(iter(devs())) if callable(devs) else self.value.device
        except Exception:
            return "unknown"

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from ..tensor import manipulation as M
        return M.transpose(self, list(range(self.ndim))[::-1])

    @property
    def mT(self):
        from ..tensor import manipulation as M
        perm = list(range(self.ndim))
        perm[-1], perm[-2] = perm[-2], perm[-1]
        return M.transpose(self, perm)

    # ---- grad surface ----
    @property
    def grad(self):
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True, name=self.name + "@GRAD")

    @grad.setter
    def grad(self, g):
        self._grad = None if g is None else (g.value if isinstance(g, Tensor) else jnp.asarray(g))

    def _accumulate_grad(self, g):
        # GradNodeAccumulation parity (paddle/fluid/eager/accumulation/).
        self._grad = g if self._grad is None else self._grad + g

    def backward(self, grad_tensor=None, retain_graph=False):
        from ..autograd.tape import backward
        backward([self], None if grad_tensor is None else [grad_tensor],
                 retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        self._retain_grads = True

    def detach(self):
        t = Tensor(self.value, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    # ---- conversion ----
    def numpy(self):
        return np.asarray(self.value)

    def item(self, *args):
        return self.value.item(*args)

    def tolist(self):
        return np.asarray(self.value).tolist()

    def astype(self, dtype):
        from ..autograd.tape import apply
        dt = convert_dtype(dtype)
        return apply(lambda x: x.astype(dt), self, _op_name="cast")

    cast = astype

    def clone(self):
        from ..autograd.tape import apply
        return apply(lambda x: x + 0, self, _op_name="clone")

    def to(self, *args, **kwargs):
        # device moves are PJRT placements; dtype moves are casts
        out = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (str, np.dtype)) and str(a) in (
                    "float32", "float16", "bfloat16", "float64",
                    "int32", "int64"):
                out = out.astype(a)
            elif isinstance(a, str):
                dev = _resolve_device(a)
                if dev is not None:
                    moved = jax.device_put(out.value, dev)
                    t = Tensor(moved, stop_gradient=out.stop_gradient)
                    # keep the autograd chain: a device move is identity
                    # for gradients
                    t._node, t._out_index = out._node, out._out_index
                    out = t
        return out

    def cpu(self):
        return Tensor(np.asarray(self.value), stop_gradient=self.stop_gradient)

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    # ---- mutation (in-place API parity) ----
    def _replace_(self, new: "Tensor"):
        """Rebind payload+autograd meta in place (inplace-op semantics)."""
        self.value = new.value
        self._node = new._node
        self._out_index = new._out_index
        self.stop_gradient = new.stop_gradient
        return self

    def _inplace_(self, fn, *args, **kwargs):
        """Run `fn` on a SNAPSHOT of this tensor, then rebind the result
        in place. The snapshot matters for autograd: `x._replace_(fn(x))`
        would make the new node's recorded input be the replaced tensor
        itself — a self-referential edge that silently drops upstream
        gradients. The snapshot preserves the pre-update node, so
        backward chains inplace ops exactly like their out-of-place
        forms (reference inplace-op autograd semantics)."""
        snap = Tensor(self.value, stop_gradient=self.stop_gradient)
        snap._node = self._node
        snap._out_index = self._out_index
        return self._replace_(fn(snap, *args, **kwargs))

    def set_value(self, v):
        if isinstance(v, Tensor):
            v = v.value
        if isinstance(v, jax.Array):
            # copy: the fused optimizer step donates param buffers, so this
            # tensor must not alias a buffer owned by another Tensor
            v = jnp.copy(v)
        self.value = jnp.asarray(v, dtype=self.value.dtype).reshape(self.value.shape)
        return self

    def copy_(self, other):
        return self.set_value(other)

    def fill_(self, v):
        self.value = jnp.full_like(self.value, v)
        return self

    def zero_(self):
        return self.fill_(0)

    # ---- indexing ----
    def __getitem__(self, idx):
        from ..autograd.tape import apply
        idx = _index_to_raw(idx)
        return apply(lambda x: x[idx], self, _op_name="getitem")

    def __setitem__(self, idx, v):
        from ..autograd.tape import apply
        idx = _index_to_raw(idx)
        if isinstance(v, Tensor):
            new = apply(lambda x, u: x.at[idx].set(u.astype(x.dtype)), self, v,
                        _op_name="setitem")
        else:
            new = apply(lambda x: x.at[idx].set(v), self, _op_name="setitem")
        self._replace_(new)

    # ---- python protocol ----
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self.value.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __float__(self):
        # THE scalar device->host sync; counted so the fused train loop's
        # zero-mid-window-sync guarantee is assertable (framework.syncs)
        from ..framework import syncs
        syncs.record_sync()
        return float(self.value)

    def __int__(self):
        return int(self.value)

    def __bool__(self):
        return bool(self.value)

    def __index__(self):
        return int(self.value)

    def __hash__(self):
        return id(self)

    def __array__(self, dtype=None):
        a = np.asarray(self.value)
        return a.astype(dtype) if dtype is not None else a

    def __repr__(self):
        prefix = "Tensor(shape={}, dtype={}, stop_gradient={},\n       ".format(
            self.shape, self.dtype.name if hasattr(self.dtype, "name") else self.dtype,
            self.stop_gradient)
        try:
            body = np.array2string(np.asarray(self.value), prefix=" " * 7)
        except Exception:
            body = "<traced>"
        return prefix + body + ")"

    def __dlpack__(self, *a, **k):
        return self.value.__dlpack__(*a, **k)


def _index_to_raw(idx):
    if isinstance(idx, Tensor):
        return idx.value
    if isinstance(idx, tuple):
        return tuple(i.value if isinstance(i, Tensor) else i for i in idx)
    return idx


def as_raw(t):
    """Unwrap a Tensor to its jax array; pass arrays/scalars through."""
    return t.value if isinstance(t, Tensor) else jnp.asarray(t)


def _wrap_single(value):
    return Tensor(value, stop_gradient=True)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity (python/paddle/tensor/creation.py)."""
    if isinstance(data, Tensor):
        v = data.value
    else:
        v = data
    dt = convert_dtype(dtype)
    if isinstance(v, (list, tuple)):
        v = np.asarray(v)
    if dt is None and isinstance(v, np.ndarray) and v.dtype == np.float64:
        dt = np.dtype(np.float32)  # paddle default-dtype semantics
    arr = jnp.asarray(v, dtype=dt)
    return Tensor(arr, stop_gradient=stop_gradient)


class Parameter(Tensor):
    """Trainable tensor: stop_gradient defaults to False.

    Parity: paddle Parameter / EagerParamBase (fluid/framework.py).
    """
    __slots__ = ("trainable", "optimize_attr", "regularizer", "is_distributed",
                 "sharding_axes", "need_clip")

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True
        self.persistable = True
        # PartitionSpec-style annotation consumed by the pjit path
        # (role of dist_attr in reference auto_parallel).
        self.sharding_axes = None

    @property
    def trainable_(self):
        return not self.stop_gradient
