"""Linear-attention functionals: the gated delta rule (Gated DeltaNet,
arXiv:2412.06464) and the two pieces that feed it, the causal depthwise
short convolution and the per-head L2 norm.

``gated_delta_rule`` takes the kernel's own head-major layout, q and k
[B, H, S, dk], v [B, H, S, dv], g (log of the decay) and beta [B, H, S],
with q's scale already on q, and returns o [B, H, S, dv] in v's dtype. On
a TPU it is the chunked Pallas kernel of ``kernels/gated_delta.py``,
forward and backward; elsewhere the same chunk bodies under ``lax.scan``
(the XLA path). A sequence that is no multiple of the chunk is padded at
its end with positions that change nothing before them (k = v = 0, no
decay).

``last_linear_attention_dispatch()`` says what the last traced call did:
``backend`` ("pallas" | "xla"), ``reason``, ``chunk``, ``heads``, ``dk``
and ``dv``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...autograd.tape import apply
from ...kernels.gated_delta import gated_delta_pallas, gated_delta_xla

__all__ = ["gated_delta_rule", "short_convolution", "l2_normalize",
           "last_linear_attention_dispatch"]

_last_dispatch: dict = {}


def last_linear_attention_dispatch() -> dict:
    """The most recent ``gated_delta_rule`` dispatch decision."""
    return dict(_last_dispatch)


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
                          chunk=chunk, heads=heads, dk=dk, dv=v.shape[-1])

    def f(q, k, v, g, beta):
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


def short_convolution(x, weight):
    """Causal depthwise convolution along the sequence, zero-padded at its
    start and without bias, then SiLU: x [B, H, S, d] head-major, weight
    [H * d, K] (channel c = h * d + i, tap j reads position t - K + 1 + j),
    computed in float32."""
    def f(xv, w):
        heads, width = xv.shape[1], xv.shape[3]
        taps = w.shape[-1]
        w = w.astype(jnp.float32).reshape(heads, width, taps)
        xf = jnp.pad(xv.astype(jnp.float32),
                     [(0, 0), (0, 0), (taps - 1, 0), (0, 0)])
        seq = xv.shape[2]
        y = sum(xf[:, :, j:j + seq] * w[None, :, None, :, j]
                for j in range(taps))
        return jax.nn.silu(y).astype(xv.dtype)

    return apply(f, x, weight, _op_name="short_convolution")


def l2_normalize(x, epsilon: float = 1e-6, scale: float = 1.0):
    """x * rsqrt(sum(x^2) + epsilon) over the last axis (each head of a
    head-major array), times ``scale``, in float32."""
    def f(xv):
        xf = xv.astype(jnp.float32)
        n = jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + epsilon)
        return (xf * (n * scale)).astype(xv.dtype)

    return apply(f, x, _op_name="l2_normalize")
