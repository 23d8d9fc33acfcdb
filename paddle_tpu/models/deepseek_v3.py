"""deepseek_v3 decoder family (DeepSeek-V3's architecture as transformers'
``modeling_deepseek_v3.py`` writes it; kakaocorp/kanana-2-30b-a3b is one)
for training.

A decoder of pre-norm blocks, two RMSNorms each. Attention is multi-head
latent attention (MLA) without a query bottleneck (``q_lora_rank`` null):
keys and values are expanded from one ``kv_lora_rank``-wide latent a
token, RMS-normed; a query or key head is ``qk_nope_head_dim`` columns
without position beside ``qk_rope_head_dim`` rotary columns, the key's
rotary columns ONE head shared by all; a value head is ``v_head_dim``
wide. So q and k heads (192) are wider than v heads (128), and the softmax
scale is ``(nope + rope) ** -0.5``. The first ``first_k_dense_replace``
layers have a dense SwiGLU MLP, the others a dropless token-choice expert
layer (``distributed.moe.TokenChoiceMoE``: sigmoid scores over every
published expert, top-k with a balancing bias in the choice only,
normalised, times ``routed_scaling_factor``) beside ``n_shared_experts``
shared experts as one SwiGLU of their summed width. Untied head.

The training path writes q, k, v in the attention kernel's own
[B, heads, S, d] layout from the projections and reads the context from it
(``head_major_attention``, as ``models/gpt.py``): q's scale goes on its
projection's float32 accumulator (RoPE is linear, so it commutes), the
rotary columns are turned where S is still the second axis
(``models/llama.py``'s ``_rope``, interleaved pairs) and joined to the rest
in the one pass that lays q and k out, k's broadcast over the heads there.

Layers are unrolled: ``models/scanned.py`` refuses blocks with buffers, and
the expert layer keeps three. ``recompute`` checkpoints each block, the
expert layer's counts coming out of the block as a value and its buffers
updated outside it, as ``models/afmoe.py``. One chip's share of expert
parallelism is a configuration: ``experts_held`` of the ``n_routed_experts``
published, from ``expert_offset`` on.
"""
from __future__ import annotations

from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional

import jax.numpy as jnp

from .. import tensor as T
from ..autograd.tape import apply
from ..distributed.meta_parallel import VocabParallelEmbedding
from ..distributed.meta_parallel.mp_layers import _constrain
from ..distributed.moe import TokenChoiceMoE
from ..nn import initializer as I
from ..nn import Linear, RMSNorm
from ..nn.functional.flash_attention import head_major_attention
from ..nn.layer_base import Layer
from .gpt import GPTForCausalLM
from .llama import LlamaMLP, _rope

__all__ = ["DeepseekV3Config", "DeepseekV3Attention", "DeepseekV3Block",
           "DeepseekV3Model", "DeepseekV3ForCausalLM"]


@dataclass
class DeepseekV3Config:
    """The defaults are kakaocorp/kanana-2-30b-a3b-instruct-2601's
    published config.json (30B-A3B)."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144           # the leading dense layers
    moe_intermediate_size: int = 768        # each routed expert
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 128             # published: the router's width
    experts_held: Optional[int] = None      # None: all of them live here
    expert_offset: int = 0
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    # the balancing bias's step (arXiv:2408.15664); the config gives none
    bias_update_rate: float = 1e-3
    initializer_range: float = 0.02
    max_seq_len: int = 32768
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

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class _HeadsOut(Layer):
    """x [B, S, in] times ``weight`` [in, heads * width] viewed
    [in, heads, width]: the columns ``cols`` of every head as
    [B, heads, S, n] (``head_major``) or [B, S, heads, n], ``scale`` put on
    the float32 accumulator before the one rounding. Column-parallel:
    heads over "mp"."""

    def __init__(self, in_features, heads, width, init):
        super().__init__()
        self.heads, self.width = heads, width
        self.weight = self.create_parameter([in_features, heads * width],
                                            default_initializer=init)
        self.weight.sharding_axes = (None, "mp")

    def forward(self, x, cols=None, scale=1.0, head_major=True):
        lo, hi = cols or (0, self.width)
        out = "bhsd" if head_major else "bshd"

        def project(xv, w):
            acc_t = jnp.promote_types(xv.dtype, jnp.float32)
            w = w.reshape(-1, self.heads, self.width)[:, :, lo:hi]
            acc = jnp.einsum("bsk,khd->" + out, xv, w.astype(xv.dtype),
                             preferred_element_type=acc_t)
            return (acc * scale).astype(xv.dtype)

        y = apply(project, x, self.weight, _op_name="linear")
        return _constrain(y, *((None, "mp", None, None) if head_major
                               else (None, None, "mp", None)))


class _HeadsIn(Layer):
    """A context [B, heads, S, width] times ``weight`` [heads * width, out]:
    one contraction over (heads, width). Row-parallel: partial products
    reduced over "mp"."""

    def __init__(self, heads, width, out_features, init):
        super().__init__()
        self.heads, self.width = heads, width
        self.weight = self.create_parameter([heads * width, out_features],
                                            default_initializer=init)
        self.weight.sharding_axes = ("mp", None)

    def forward(self, ctx):
        y = apply(lambda c, w: jnp.einsum(
            "bhsd,hdk->bsk", c,
            w.reshape(self.heads, self.width, -1).astype(c.dtype)),
            ctx, self.weight, _op_name="linear")
        return _constrain(y, None, None, None)


class DeepseekV3Attention(Layer):
    """Multi-head latent attention, causal, q and k heads of
    ``qk_nope_head_dim + qk_rope_head_dim`` columns and v heads of
    ``v_head_dim``."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        self.num_heads = nh
        self.nope, self.rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.v_dim, self.rank = cfg.v_head_dim, cfg.kv_lora_rank
        self.theta = cfg.rope_theta
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = _HeadsOut(h, nh, cfg.qk_head_dim, init)
        # the latent and the one shared rotary key head, in one product
        self.kv_a_proj = Linear(h, self.rank + self.rope, weight_attr=init,
                                bias_attr=False)
        self.kv_a_norm = RMSNorm(self.rank, cfg.rms_norm_eps)
        self.kv_b_proj = _HeadsOut(self.rank, nh, self.nope + self.v_dim,
                                   init)
        self.o_proj = _HeadsIn(nh, self.v_dim, h, init)

    def forward(self, x):
        B, S, _ = x.shape
        nh, nope, rope = self.num_heads, self.nope, self.rope
        scale = (nope + rope) ** -0.5
        q_nope = self.q_proj(x, (0, nope), scale)
        q_pe = self.q_proj(x, (nope, nope + rope), scale, head_major=False)
        kv = self.kv_a_proj(x)
        k_pe = T.reshape(kv[:, :, self.rank:], [B, S, 1, rope])
        c = self.kv_a_norm(kv[:, :, :self.rank])
        k_nope = self.kv_b_proj(c, (0, nope))
        v = self.kv_b_proj(c, (nope, nope + self.v_dim))
        q_pe, k_pe = _rope(q_pe, k_pe, self.theta)

        def join(a, pe):        # [B, nh, S, nope] with [B, S, 1 or nh, rope]
            pe = jnp.broadcast_to(jnp.swapaxes(pe, 1, 2), (B, nh, S, rope))
            return jnp.concatenate([a, pe], axis=-1)

        q = apply(join, q_nope, q_pe, _op_name="mla_join")
        k = apply(join, k_nope, k_pe, _op_name="mla_join")
        return self.o_proj(head_major_attention(q, k, v, causal=True))


def _swiglu(hidden: int, width: int, cfg: DeepseekV3Config):
    return LlamaMLP(SimpleNamespace(
        hidden_size=hidden, intermediate_size=width,
        initializer_range=cfg.initializer_range))


class DeepseekV3Block(Layer):
    """One layer. An expert layer's block returns ``(x, counts)``, the
    counts of tokens by published expert, so that it can be recomputed;
    a dense layer's returns x."""

    def __init__(self, cfg: DeepseekV3Config, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.is_moe = index >= cfg.first_k_dense_replace
        self.input_layernorm = RMSNorm(h, eps)
        self.attn = DeepseekV3Attention(cfg)
        self.post_attention_layernorm = RMSNorm(h, eps)
        if self.is_moe:
            shared = None
            if cfg.n_shared_experts:
                shared = _swiglu(h, cfg.moe_intermediate_size
                                 * cfg.n_shared_experts, cfg)
            self.mlp = TokenChoiceMoE(
                h, cfg.moe_intermediate_size, cfg.n_routed_experts,
                cfg.num_experts_per_tok, experts_held=cfg.experts_held,
                expert_offset=cfg.expert_offset, shared_expert=shared,
                route_norm=cfg.norm_topk_prob,
                route_scale=cfg.routed_scaling_factor,
                bias_update_rate=cfg.bias_update_rate,
                initializer_range=cfg.initializer_range)
        else:
            self.mlp = _swiglu(h, cfg.intermediate_size, cfg)

    def forward(self, x):
        x = x + self.attn(self.input_layernorm(x))
        y = self.mlp(self.post_attention_layernorm(x))
        if self.is_moe:
            y, counts = y
            return x + y, counts
        return x + y


class DeepseekV3Model(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.blocks = []
        for i in range(cfg.num_hidden_layers):
            blk = DeepseekV3Block(cfg, i)
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


class DeepseekV3ForCausalLM(Layer):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        self.model = DeepseekV3Model(cfg)
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
