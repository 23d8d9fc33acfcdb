"""Activation recomputation (gradient checkpointing).

Parity: python/paddle/distributed/fleet/recompute/recompute.py:69
(RecomputeFunction PyLayer — saves inputs + RNG state, re-runs forward in
backward) and recompute_hybrid.py (mp-sharded saved activations).
TPU-native: `jax.checkpoint` IS this mechanism — XLA rematerializes the
forward inside the backward, RNG is already functional (keys are values,
nothing to snapshot), and under hybrid parallel the rematerialized
activations inherit their sharding constraints, subsuming the reference's
_split_activation/_merge_activation partitioning (recompute_hybrid.py:31,55).
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax

from ..autograd import tape as _tape
from ..core.tensor import Tensor
from ..jit.functional import functional_call, raw_state, _wrap
from ..kernels.gated_delta import DELTA_RULE_RESIDUAL
from ..nn.functional.flash_attention import ATTENTION_RESIDUAL
from ..nn.layer_base import Layer
from .moe import EXPERTS_RESULT

__all__ = ["recompute", "recompute_sequential"]


# named remat policies. Both keep the attention kernel's result and
# logsumexp (flash_attention.ATTENTION_RESIDUAL: one [b, h, s, d_v]
# activation and one f32 [b, h, s] a layer), so the backward pass never
# runs the forward kernel a second time: per kept byte they are the
# dearest thing in a block to recompute (about s_k kernel operations a
# byte at a third of a matmul's rate; a matmul's output costs H).
# They keep the token-choice expert layer's result too where the backward
# pass reads it (moe.EXPERTS_RESULT, one [tokens, d] activation a layer):
# its conditional runs its path again inside its own backward branch, so
# a recomputed forward conditional would be a third run.
# And they keep the delta rule's output and float32 chunk states
# (gated_delta.DELTA_RULE_RESIDUAL: one [b, h, s, d_v] activation and one
# f32 [b, h, s / chunk, d_k, d_v] a linear-attention layer, 0.24 GB at
# 30 heads of 96 x 192 and 8,192 positions), which its backward kernel
# reads: a few hundred float32 operations a kept byte at a sixth to a
# third of the MXU's bf16 rate, the forward kernel's whole run (6.3 ms a
# layer on a v5e at those sizes).
# "full" keeps the block's input and those, and recomputes everything
# else; "dots" keeps matmul outputs besides (recomputes only
# elementwise/norm ops — trades HBM for a ~1/3 cut in recompute FLOPs).
# To keep nothing but the block's input (least memory) hand in the
# callable jax.checkpoint_policies.nothing_saveable.
_POLICIES = {"full": None, "dots": "dots_with_no_batch_dims_saveable"}


def resolve_checkpoint_policy(policy):
    """Resolve a policy name ("full"/"dots"), a jax.checkpoint_policies
    callable, or None into the `policy=` argument for jax.checkpoint. A
    callable (and None, jax.checkpoint's own default) passes through
    untouched."""
    if policy is None or callable(policy):
        return policy
    try:
        name = _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"recompute policy {policy!r} not in {sorted(_POLICIES)} "
            "(or pass a jax.checkpoint_policies callable)") from None
    cp = jax.checkpoint_policies
    keep = cp.save_only_these_names(ATTENTION_RESIDUAL, EXPERTS_RESULT,
                                    DELTA_RULE_RESIDUAL)
    return cp.save_from_both_policies(getattr(cp, name), keep) if name \
        else keep


def recompute(function, *args, **kwargs):
    """Parity: paddle.distributed.fleet.utils.recompute.

    `function` is a Layer (or a Layer's __call__); its forward is re-run
    during backward instead of saving activations. Extra kwargs
    (use_reentrant, preserve_rng_state) are accepted for API parity —
    rematerialization on XLA is always "non-reentrant" and RNG-correct.
    TPU extension: `policy=` ("full"/"dots" or a jax.checkpoint_policies
    callable) selects what the remat saves.
    """
    kwargs.pop("use_reentrant", None)
    kwargs.pop("preserve_rng_state", None)
    ckpt_policy = resolve_checkpoint_policy(kwargs.pop("policy", None))
    layer = function
    if not isinstance(layer, Layer):
        layer = getattr(function, "__self__", None)
        if not isinstance(layer, Layer):
            raise TypeError(
                "recompute requires a Layer (parameters must be visible to "
                "the remat boundary); wrap plain functions in a Layer")

    params, buffers = raw_state(layer)
    pnames = list(params)
    tensor_args = [a for a in args if isinstance(a, Tensor)]
    other_mask = [isinstance(a, Tensor) for a in args]
    # kwarg Tensors must also cross the remat boundary as tape inputs or
    # their gradients are silently dropped
    kw_tensor_keys = [k for k, v in kwargs.items() if isinstance(v, Tensor)]
    kw_tensors = [kwargs[k] for k in kw_tensor_keys]
    static_kwargs = {k: v for k, v in kwargs.items()
                     if k not in kw_tensor_keys}

    @functools.partial(jax.checkpoint, policy=ckpt_policy)
    def rematted(flat_params, *arr_args):
        p = dict(zip(pnames, flat_params))
        n_kw = len(kw_tensor_keys)
        pos_arrs = arr_args[:len(arr_args) - n_kw]
        kw_arrs = arr_args[len(arr_args) - n_kw:]
        rebuilt, it = [], iter(pos_arrs)
        for a, is_t in zip(args, other_mask):
            rebuilt.append(next(it) if is_t else a)
        kw = dict(static_kwargs)
        kw.update({k: Tensor(v) for k, v in zip(kw_tensor_keys, kw_arrs)})
        out, _ = functional_call(layer, p, buffers, *rebuilt,
                                 training=layer.training, **kw)
        return out

    param_tensors = [dict(layer.named_parameters())[n] for n in pnames]

    def fn(*flat):
        return rematted(list(flat[:len(pnames)]), *flat[len(pnames):])

    return _tape.apply(fn, *param_tensors, *tensor_args, *kw_tensors,
                       _op_name="recompute")


def recompute_sequential(ctx, functions, *args):
    """Parity: paddle.incubate.distributed.fleet.recompute_sequential —
    checkpoint every segment of a Sequential."""
    segments = int(ctx.get("segments", 1)) if isinstance(ctx, dict) else 1
    layers = list(functions)
    n = len(layers)
    per = max(n // max(segments, 1), 1)
    out = args
    i = 0
    while i < n:
        seg = layers[i:i + per]
        import paddle_tpu.nn as nn
        block = seg[0] if len(seg) == 1 else nn.Sequential(*seg)
        out = (recompute(block, *out),)
        i += per
    return out[0]
