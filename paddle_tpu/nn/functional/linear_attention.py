"""Linear-attention functionals: the gated delta rule (Gated DeltaNet,
arXiv:2412.06464) and what feeds it, the causal depthwise short
convolution with SiLU and the per-head L2 norm.

``gated_delta_rule`` takes the kernel's own head-major layout, q and k
[B, H, S, dk], v [B, H, S, dv], g (log of the decay) and beta [B, H, S],
with q's scale already on q, and returns o [B, H, S, dv] in v's dtype. On
a TPU it is the chunked Pallas kernel of ``kernels/gated_delta.py``,
forward and backward; elsewhere the same chunk bodies under ``lax.scan``
(the XLA path). A sequence that is no multiple of the chunk is padded at
its end with positions that change nothing before them (k = v = 0, no
decay).

``last_linear_attention_dispatch()`` says what the last traced call did:
``backend`` ("pallas" | "xla"), ``reason``, ``chunk``, ``heads``, ``dk``,
``dv`` and ``split_products``: how many products of the forward and of the
backward chunk body took the three-pass form (a float32 operand split
against a bfloat16 q, k, v or output gradient), counted as the bodies were
traced with operands of the call's dtypes: ``{"forward": 4, "backward":
14}`` on bfloat16 operands, zeros on float32 ones; and ``residual``, the
name (``DELTA_RULE_RESIDUAL``) under which the forward rule hands its output
and chunk states to recomputation, on both backends.

``short_convolution`` takes the projection's head-major [B, H, S, d] and
runs the causal depthwise convolution, SiLU and, with ``normalize``, the
per-head L2 norm and a scale as one chain in float32 with one rounding: on
a TPU the two Pallas kernels of ``kernels/short_conv.py``, elsewhere the
same chain in ``jax.numpy``. Its record is the record's ``conv``:
``backend``, ``reason`` and ``tile`` (None on the XLA path) of the last
call, and ``l2``, each width called -> whether its last call took the L2
epilogue (q and k 96: True, v 192: False in the Olmo Hybrid mixer).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.tape import apply
from ...kernels.gated_delta import (DELTA_RULE_RESIDUAL, gated_delta_pallas,
                                    gated_delta_xla, split_products)
from ...kernels.short_conv import HALO, short_conv_pallas, short_conv_xla

__all__ = ["gated_delta_rule", "short_convolution",
           "last_linear_attention_dispatch"]

_last_dispatch: dict = {}
_last_dtypes: list = []     # q, k, v of the last call
_last_conv: dict = {}       # the last short convolution's, and l2 by width

# the short convolution's sequence tile (kernels/short_conv.py)
CONV_TILE = 1024


def last_linear_attention_dispatch() -> dict:
    """The most recent ``gated_delta_rule`` dispatch decision."""
    rec = dict(_last_dispatch)
    if _last_dtypes:
        rec["split_products"] = split_products(*_last_dtypes)
    if _last_conv:
        rec["conv"] = dict(_last_conv, l2=dict(_last_conv["l2"]))
    return rec


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Whether a Pallas path runs through the interpreter: off the chip
    (a test that sends the call down the Pallas path on the CPU)."""
    return jax.default_backend() != "tpu"


def gated_delta_rule(q, k, v, g, beta, chunk: int = 128):
    """o_t = S_t q_t, S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T)
    + beta_t v_t k_t^T from S_0 = 0, per head; state in float32."""
    _, heads, seq, dk = q.shape
    pallas = _on_tpu()
    _last_dispatch.clear()
    _last_dispatch.update(backend="pallas" if pallas else "xla",
                          reason="ok" if pallas else "not on TPU",
                          chunk=chunk, heads=heads, dk=dk, dv=v.shape[-1],
                          residual=DELTA_RULE_RESIDUAL)

    def f(q, k, v, g, beta):
        _last_dtypes[:] = [q.dtype, k.dtype, v.dtype]
        pad = -seq % chunk
        if pad:
            widen = lambda x: jnp.pad(
                x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
            q, k, v, g, beta = map(widen, (q, k, v, g, beta))
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        o = gated_delta_pallas(q, k, v, g, beta, chunk, _interpret()) \
            if pallas else gated_delta_xla(q, k, v, g, beta, chunk)
        return o[:, :, :seq]

    return apply(f, q, k, v, g, beta, _op_name="gated_delta_rule")


def short_convolution(x, weight, normalize: bool = False,
                      epsilon: float = 1e-6, scale: float = 1.0):
    """Causal depthwise convolution along the sequence, zero-padded at its
    start and without bias, then SiLU and, with ``normalize``, ``x *
    rsqrt(sum(x^2) + epsilon) * scale`` over each head: x [B, H, S, d]
    head-major, weight [H * d, K] (channel c = h * d + i, tap j reads
    position t - K + 1 + j), computed in float32 and rounded once to x's
    dtype. A sequence that is no multiple of the kernel's tile is padded at
    its end (nothing before a position reads it) and cut back."""
    _, heads, seq, width = x.shape
    pallas = _on_tpu()
    tile = min(CONV_TILE, -(-seq // HALO) * HALO) if pallas else None
    _last_conv.update(backend="pallas" if pallas else "xla",
                      reason="ok" if pallas else "not on TPU", tile=tile)
    _last_conv.setdefault("l2", {})[width] = normalize

    def f(xv, w):
        taps = w.shape[-1]
        w = jnp.swapaxes(w.reshape(heads, width, taps), 1, 2)   # [H, K, d]
        if not pallas:
            return short_conv_xla(xv, w, normalize, epsilon, scale)
        pad = -seq % tile
        if pad:
            xv = jnp.pad(xv, [(0, 0), (0, 0), (0, pad), (0, 0)])
        y = short_conv_pallas(xv, w, tile, normalize, epsilon, scale,
                              _interpret())
        return y[:, :, :seq]

    return apply(f, x, weight, _op_name="short_convolution")

