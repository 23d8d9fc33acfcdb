"""afmoe decoder family (Arcee Trinity: Trinity-Mini 26B-A3B, Nano) for
training.

A decoder whose layers are unlike one another: ``num_dense_layers``
leading layers with a dense SwiGLU MLP, then layers whose MLP is a
dropless token-choice expert layer (``distributed.moe.TokenChoiceMoE``:
sigmoid scores over every published expert, top-k, a balancing bias, one
shared expert); attention by ``layer_types``, ``sliding_attention`` (a
causal window, with RoPE) or ``full_attention`` (causal, no position at
all), both grouped-query through ``F.flash_attention``; q and k
RMS-normed per head; the attention output gated by ``sigmoid(x Wg)``
before ``o_proj``; four RMSNorms a layer (in, post-attention, pre-MLP,
post-MLP: each sublayer's output is normed before it joins the stream);
embeddings scaled by ``sqrt(hidden_size)`` (muP); an untied head.

Built from ``models/llama.py``'s ``_rope`` and SwiGLU ``LlamaMLP`` and
``nn.RMSNorm``. Layers are unrolled (``models/scanned.py`` knows no
periods of unlike layers); ``recompute`` checkpoints each block, the
expert layer's counts coming out of the block as a value and the buffers
``expert_bias`` / ``expert_load`` updated outside it. One chip's share of
expert parallelism is a configuration: ``experts_held`` of the
``num_experts`` published, from ``expert_offset`` on.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional, Sequence

from .. import tensor as T
from ..distributed.meta_parallel import (ColumnParallelLinear,
                                         RowParallelLinear,
                                         VocabParallelEmbedding)
from ..distributed.moe import TokenChoiceMoE
from ..nn import functional as F
from ..nn import initializer as I
from ..nn import Linear, RMSNorm
from ..nn.layer_base import Layer
from .gpt import GPTForCausalLM
from .llama import LlamaMLP, _rope

__all__ = ["AfmoeConfig", "AfmoeModel", "AfmoeForCausalLM"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    """The defaults are arcee-ai/Trinity-Mini's published config.json
    (26B-A3B)."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144           # the leading dense layers
    moe_intermediate_size: int = 1024       # each routed and shared expert
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    # one of SLIDING / FULL a layer; None: every `global_attn_every_n_layers`th
    # layer full, the others sliding
    layer_types: Optional[Sequence[str]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 128                  # published: the router's width
    experts_held: Optional[int] = None      # None: all of them live here
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    mup_enabled: bool = True
    initializer_range: float = 0.02
    max_seq_len: int = 131072
    # rematerialize each block in backward (jax.checkpoint)
    recompute: bool = False
    # what a recomputed block keeps beside its input: "full" the attention
    # kernel's result and logsumexp, "dots" matmul outputs too; a
    # jax.checkpoint_policies callable (nothing_saveable: keep nothing)
    # passes through (distributed/recompute.py)
    recompute_policy: str = "full"
    # when >0, a training forward returns (hidden, lm_weight) and the loss
    # streams the head through F.fused_linear_cross_entropy in chunks
    fused_loss_chunk: int = 0

    def kinds(self):
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
            if len(kinds) != self.num_hidden_layers or \
                    set(kinds) - {SLIDING, FULL}:
                raise ValueError(
                    f"layer_types must name each of the "
                    f"{self.num_hidden_layers} layers {SLIDING!r} or "
                    f"{FULL!r}")
            return kinds
        n = self.global_attn_every_n_layers
        return tuple(FULL if (i + 1) % n == 0 else SLIDING
                     for i in range(self.num_hidden_layers))


class AfmoeAttention(Layer):
    """Grouped-query attention, causal or in a causal window, q and k
    normed per head, the output gated."""

    def __init__(self, cfg: AfmoeConfig, kind: str):
        super().__init__()
        h, hd = cfg.hidden_size, cfg.head_dim
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.num_heads, self.kv_heads, self.head_dim = nh, nkv, hd
        self.sliding = kind == SLIDING
        self.window = cfg.sliding_window if self.sliding else None
        self.theta = cfg.rope_theta
        init = I.Normal(0.0, cfg.initializer_range)

        def col(n_out):
            return ColumnParallelLinear(h, n_out, weight_attr=init,
                                        has_bias=False, gather_output=False)
        self.q_proj, self.k_proj = col(nh * hd), col(nkv * hd)
        self.v_proj, self.gate_proj = col(nkv * hd), col(nh * hd)
        self.o_proj = RowParallelLinear(nh * hd, h, weight_attr=init,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.q_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.k_norm = RMSNorm(hd, cfg.rms_norm_eps)

    def forward(self, x):
        B, S, _ = x.shape
        nh, nkv, hd = self.num_heads, self.kv_heads, self.head_dim
        q = self.q_norm(T.reshape(self.q_proj(x), [B, S, nh, hd]))
        k = self.k_norm(T.reshape(self.k_proj(x), [B, S, nkv, hd]))
        v = T.reshape(self.v_proj(x), [B, S, nkv, hd])
        if self.sliding:    # full layers take no position at all
            q, k = _rope(q, k, self.theta)
        ctx, _ = F.flash_attention(q, k, v, causal=True,
                                   training=self.training,
                                   window=self.window)
        ctx = T.reshape(ctx, [B, S, nh * hd]) * F.sigmoid(self.gate_proj(x))
        return self.o_proj(ctx)


def _swiglu(hidden: int, width: int, cfg: AfmoeConfig):
    return LlamaMLP(SimpleNamespace(
        hidden_size=hidden, intermediate_size=width,
        initializer_range=cfg.initializer_range))


class AfmoeBlock(Layer):
    """One layer. An expert layer's block returns ``(x, counts)``, the
    counts of tokens by published expert, so that it can be recomputed;
    a dense layer's returns x."""

    def __init__(self, cfg: AfmoeConfig, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.is_moe = index >= cfg.num_dense_layers
        self.input_layernorm = RMSNorm(h, eps)
        self.attn = AfmoeAttention(cfg, cfg.kinds()[index])
        self.post_attention_layernorm = RMSNorm(h, eps)
        self.pre_mlp_layernorm = RMSNorm(h, eps)
        if self.is_moe:
            shared = None
            if cfg.num_shared_experts:
                shared = _swiglu(h, cfg.moe_intermediate_size
                                 * cfg.num_shared_experts, cfg)
            self.mlp = TokenChoiceMoE(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, experts_held=cfg.experts_held,
                expert_offset=cfg.expert_offset, shared_expert=shared,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                bias_update_rate=cfg.load_balance_coeff,
                initializer_range=cfg.initializer_range)
        else:
            self.mlp = _swiglu(h, cfg.intermediate_size, cfg)
        self.post_mlp_layernorm = RMSNorm(h, eps)

    def forward(self, x):
        x = x + self.post_attention_layernorm(
            self.attn(self.input_layernorm(x)))
        y = self.mlp(self.pre_mlp_layernorm(x))
        if self.is_moe:
            y, counts = y
            return x + self.post_mlp_layernorm(y), counts
        return x + self.post_mlp_layernorm(y)


class AfmoeModel(Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.blocks = []
        for i in range(cfg.num_hidden_layers):
            blk = AfmoeBlock(cfg, i)
            self.add_sublayer(f"block_{i}", blk)
            self.blocks.append(blk)
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, ids):
        cfg = self.cfg
        if ids.shape[-1] > cfg.max_seq_len:
            raise ValueError(
                f"sequence length {ids.shape[-1]} exceeds max_seq_len "
                f"{cfg.max_seq_len}")
        x = self.embed_tokens(ids)
        if cfg.mup_enabled:
            x = x * math.sqrt(cfg.hidden_size)
        remat = cfg.recompute and self.training
        if remat:
            from ..distributed.recompute import recompute as _rc
        for blk in self.blocks:
            out = _rc(blk, x, policy=cfg.recompute_policy) if remat \
                else blk(x)
            if blk.is_moe:
                x, counts = out
                if self.training:       # outside the recomputed region
                    blk.mlp.note_load(counts)
            else:
                x = out
        return self.norm(x)


class AfmoeForCausalLM(Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = AfmoeModel(cfg)
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
