"""Olmo Hybrid decoder family (allenai/Olmo-Hybrid-7B, ``olmo_hybrid``) for
training: Gated DeltaNet linear attention beside full softmax attention.

``layer_types`` names each layer's mixer; the published model repeats
(linear, linear, linear, full). Every block is OLMo 2/3's reordered
post-norm (arXiv:2501.00656): ``h = x + norm(mixer(x))``, ``y = h +
norm(mlp(h))``, the MLP SwiGLU. Untied head, no biases, no dropout.

- Linear layers (``GatedDeltaNet``, the ``fla`` library's layer at
  ``expand_v`` 2 with ``use_gate``, ``use_short_conv`` and
  ``allow_neg_eigval``): q, k, v from head-major projections, each through
  a causal depthwise convolution of ``linear_conv_kernel_dim`` and SiLU;
  q and k L2-normed by head, q scaled by ``dk ** -0.5``; ``beta = 2
  sigmoid(x Wb)`` (the 2 is ``allow_neg_eigval``: eigenvalues of the
  transition down to -1, arXiv:2411.12537); ``g = -exp(A_log)
  softplus(x Wa + dt_bias)``; the gated delta rule
  (``F.gated_delta_rule``: the chunked Pallas kernel on the chip); the
  output RMS-normed by head (one gain of ``dv`` shared by the heads) times
  ``silu(x Wg)``, then ``Wo``.
- Full layers: QK-norm over the whole q and k projections before the
  split into heads, causal attention with no positions at all
  (``rope_theta`` null), through ``head_major_attention``.

Scopes: a linear layer's mixer is ``linear_attn`` with children
``short_conv`` (the convolutions with q's and k's L2 norms: on the chip
the kernels of ``kernels/short_conv.py``, every pass), ``delta_rule`` (the
kernel, every pass) and ``gated_norm``; a full layer's is ``attn``. Layers
are unrolled; ``recompute`` checkpoints each block.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..autograd.tape import apply
from ..distributed.meta_parallel import VocabParallelEmbedding
from ..framework.dtype import convert_dtype
from ..framework.random import next_key
from ..nn import functional as F
from ..nn import initializer as I
from ..nn import Linear, RMSNorm
from ..nn.functional.flash_attention import head_major_attention
from ..nn.layer_base import Layer
from .deepseek_v3 import _HeadsIn, _HeadsOut
from .gpt import GPTForCausalLM
from .llama import LlamaMLP

__all__ = ["OlmoHybridConfig", "GatedDeltaNet", "OlmoHybridAttention",
           "OlmoHybridBlock", "OlmoHybridModel", "OlmoHybridForCausalLM"]

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


@dataclass
class OlmoHybridConfig:
    """The defaults are allenai/Olmo-Hybrid-7B's published config.json,
    its keys under their own names."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    # one entry a layer; None: the published period of _PERIOD
    layer_types: Optional[Sequence[str]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 65536
    initializer_range: float = 0.02
    recompute: bool = False
    recompute_policy: str = "full"
    # when >0, a training forward returns (hidden, lm_weight) and the loss
    # streams the head through F.fused_linear_cross_entropy in chunks
    fused_loss_chunk: int = 0

    def kinds(self) -> tuple:
        n = self.num_hidden_layers
        kinds = tuple(self.layer_types) if self.layer_types is not None \
            else tuple(_PERIOD[i % len(_PERIOD)] for i in range(n))
        if len(kinds) != n or set(kinds) - set(_PERIOD):
            raise ValueError(f"layer_types holds one of {sorted(set(_PERIOD))}"
                             f" for each of {n} layers")
        return kinds


class _LogUniform(I.Initializer):
    """``fla``'s start of the decay: ``A_log = log(U(0, 16))``, or with
    ``dt`` the bias whose softplus is ``dt`` log-uniform on [1e-3, 1e-1]
    (floored at 1e-4): ``dt + log(-expm1(-dt))``."""

    def __init__(self, dt: bool):
        self.dt = dt

    def __call__(self, shape, dtype="float32"):
        u = jax.random.uniform(next_key(), tuple(shape))
        if self.dt:
            dt = jnp.maximum(jnp.exp(u * (math.log(0.1) - math.log(1e-3))
                                     + math.log(1e-3)), 1e-4)
            v = dt + jnp.log(-jnp.expm1(-dt))
        else:
            v = jnp.log(16.0 * u)
        return v.astype(convert_dtype(dtype))


class GatedDeltaNet(Layer):
    """The linear-attention mixer of a linear layer."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h = cfg.hidden_size
        nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        if nk != nv:
            raise ValueError("the delta rule here takes as many key heads "
                             "as value heads")
        self.heads, self.dk, self.dv = nv, dk, dv
        self.eps = cfg.rms_norm_eps
        self.neg_eigval = cfg.linear_allow_neg_eigval
        init = I.Normal(0.0, cfg.initializer_range)
        taps = cfg.linear_conv_kernel_dim
        self.q_proj = _HeadsOut(h, nk, dk, init)
        self.k_proj = _HeadsOut(h, nk, dk, init)
        self.v_proj = _HeadsOut(h, nv, dv, init)
        self.g_proj = _HeadsOut(h, nv, dv, init)
        self.a_proj = Linear(h, nv, weight_attr=init, bias_attr=False)
        self.b_proj = Linear(h, nv, weight_attr=init, bias_attr=False)
        conv = I.Uniform(-1.0 / math.sqrt(taps), 1.0 / math.sqrt(taps))
        self.q_conv = self.create_parameter([nk * dk, taps],
                                            default_initializer=conv)
        self.k_conv = self.create_parameter([nk * dk, taps],
                                            default_initializer=conv)
        self.v_conv = self.create_parameter([nv * dv, taps],
                                            default_initializer=conv)
        self.A_log = self.create_parameter([nv],
                                           default_initializer=_LogUniform(False))
        self.dt_bias = self.create_parameter(
            [nv], default_initializer=_LogUniform(True))
        self.o_norm = self.create_parameter([dv],
                                            default_initializer=I.Constant(1.0))
        self.o_proj = _HeadsIn(nv, dv, h, init)

    def forward(self, x):
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        with jax.named_scope("short_conv"):     # with q's and k's L2 norms
            q = F.short_convolution(q, self.q_conv, True, self.eps,
                                    self.dk ** -0.5)
            k = F.short_convolution(k, self.k_conv, True, self.eps)
            v = F.short_convolution(v, self.v_conv)

        def gates(a, b, a_log, dt_bias):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                a + dt_bias.astype(jnp.float32))
            beta = jax.nn.sigmoid(b) * (2.0 if self.neg_eigval else 1.0)
            return jnp.swapaxes(g, 1, 2), jnp.swapaxes(beta, 1, 2)

        g, beta = apply(gates, self.a_proj(x), self.b_proj(x), self.A_log,
                        self.dt_bias, _op_name="gates")
        with jax.named_scope("delta_rule"):
            o = F.gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("gated_norm"):
            def norm(o, gate, w):
                of = o.astype(jnp.float32)
                of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True)
                                        + self.eps)
                out = of * w.astype(jnp.float32) \
                    * jax.nn.silu(gate.astype(jnp.float32))
                return out.astype(o.dtype)
            o = apply(norm, o, self.g_proj(x), self.o_norm,
                      _op_name="gated_rms_norm")
        return self.o_proj(o)


class _NormedHeads(_HeadsOut):
    """``_HeadsOut`` for whole heads with an RMSNorm over ALL of the
    projection's columns (every head at once, OLMo 2's QK-norm) and then
    ``scale``, on the float32 accumulator before the one rounding."""

    def __init__(self, in_features, heads, width, init, eps):
        super().__init__(in_features, heads, width, init)
        self.eps = eps
        self.norm = self.create_parameter([heads * width],
                                          default_initializer=I.Constant(1.0))

    def forward(self, x, scale=1.0):
        def project(xv, w, gain):
            acc = jnp.einsum("bsk,khd->bhsd", xv,
                             w.reshape(-1, self.heads, self.width).astype(
                                 xv.dtype),
                             preferred_element_type=jnp.float32)
            ms = jnp.mean(acc * acc, axis=(1, 3), keepdims=True)
            gain = gain.astype(jnp.float32).reshape(self.heads, 1, self.width)
            return (acc * jax.lax.rsqrt(ms + self.eps) * gain
                    * scale).astype(xv.dtype)

        return apply(project, x, self.weight, self.norm, _op_name="linear")


class OlmoHybridAttention(Layer):
    """Causal softmax attention without positions, QK-norm over the whole
    projections."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        nkv, hd = cfg.num_key_value_heads, cfg.hidden_size // nh
        self.head_dim = hd
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = _NormedHeads(h, nh, hd, init, cfg.rms_norm_eps)
        self.k_proj = _NormedHeads(h, nkv, hd, init, cfg.rms_norm_eps)
        self.v_proj = _HeadsOut(h, nkv, hd, init)
        self.o_proj = _HeadsIn(nh, hd, h, init)

    def forward(self, x):
        q = self.q_proj(x, self.head_dim ** -0.5)
        return self.o_proj(head_major_attention(
            q, self.k_proj(x), self.v_proj(x), causal=True))


class OlmoHybridBlock(Layer):
    """One layer of either kind, its norms after the mixer and the MLP."""

    def __init__(self, cfg: OlmoHybridConfig, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.kind = cfg.kinds()[index]
        if self.kind == "linear_attention":
            self.linear_attn = GatedDeltaNet(cfg)
        else:
            self.attn = OlmoHybridAttention(cfg)
        self.post_attention_layernorm = RMSNorm(h, eps)
        self.mlp = LlamaMLP(SimpleNamespace(
            hidden_size=h, intermediate_size=cfg.intermediate_size,
            initializer_range=cfg.initializer_range))
        self.post_feedforward_layernorm = RMSNorm(h, eps)

    def forward(self, x):
        mixer = self.linear_attn if self.kind == "linear_attention" \
            else self.attn
        x = x + self.post_attention_layernorm(mixer(x))
        return x + self.post_feedforward_layernorm(self.mlp(x))


class OlmoHybridModel(Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.blocks = []
        for i in range(cfg.num_hidden_layers):
            blk = OlmoHybridBlock(cfg, i)
            self.add_sublayer(f"block_{i}", blk)
            self.blocks.append(blk)
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, ids):
        cfg = self.cfg
        if ids.shape[-1] > cfg.max_position_embeddings:
            raise ValueError(
                f"sequence length {ids.shape[-1]} exceeds "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        x = self.embed_tokens(ids)
        remat = cfg.recompute and self.training
        if remat:
            from ..distributed.recompute import recompute as _rc
        for blk in self.blocks:
            x = _rc(blk, x, policy=cfg.recompute_policy) if remat else blk(x)
        return self.norm(x)


class OlmoHybridForCausalLM(Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = OlmoHybridModel(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              weight_attr=I.Normal(
                                  0.0, cfg.initializer_range),
                              bias_attr=False)

    def forward(self, ids):
        x = self.model(ids)
        if self.cfg.fused_loss_chunk and self.training:
            # (hidden, lm_weight [H, V]) for fused_loss_fn: the logits
            # never materialize; eval() callers always get logits
            return x, self.lm_head.weight
        return self.lm_head(x)

    # next-token loss and its chunked form: GPT's, bound to this cfg
    loss_fn = staticmethod(GPTForCausalLM.loss_fn)
    fused_loss_fn = staticmethod(GPTForCausalLM.fused_loss_fn)

    def make_loss_fn(self):
        return GPTForCausalLM.make_loss_fn(self)
