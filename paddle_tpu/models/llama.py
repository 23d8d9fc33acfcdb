"""LLaMA decoder family — BASELINE.json config 4 (LLaMA-13B, TP+PP).

Capability parity: the reference trains LLaMA-class models through Fleet
hybrid parallelism (SURVEY.md §3.4; model code lives in PaddleNLP driven by
mpu/mp_layers.py + PipelineLayer). TPU-first re-design on the same TP
layer library as GPT:

- mp: q/k/v/gate/up projections are ColumnParallelLinear, o/down are
  RowParallelLinear (Megatron layout, one GSPMD allreduce per block pair);
- GQA: num_kv_heads < num_heads supported; the attention functional takes
  the fewer key/value heads as they are (the kernel's grouped path: k and
  v are never copied out to the query heads);
- RoPE is applied to q/k on the full (pre-sp-shard) sequence;
- sp: ring attention dispatch when the "sp" mesh axis is real;
- pp: LlamaPipelineForCausalLM stacks blocks over the pp axis.

All matmul-heavy compute is bfloat16-friendly; norms/softmax accumulate in
fp32 (rms_norm upcasts internally).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from .. import tensor as T
from ..autograd.tape import apply
from ..distributed import mesh as mesh_mod
from ..distributed.meta_parallel import (ColumnParallelLinear, LayerDesc,
                                         PipelineLayer, RowParallelLinear,
                                         VocabParallelEmbedding)
from ..distributed.sequence_parallel import ring_attention
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn import Linear, RMSNorm

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPipelineForCausalLM", "llama_tiny", "llama_7b", "llama_13b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # None -> MHA
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    initializer_range: float = 0.02
    # rematerialize each block in backward (jax.checkpoint) — scan path
    recompute: bool = False
    # what a recomputed block keeps beside its input: "full" the attention
    # kernel's result and logsumexp, "dots" matmul outputs too; a
    # jax.checkpoint_policies callable (nothing_saveable: keep nothing)
    # passes through (distributed/recompute.py)
    recompute_policy: str = "full"
    # compile the block stack as ONE lax.scan over [L, ...]-stacked params
    # (models/scanned.py ScannedStack) — depth-independent HLO
    scan_layers: bool = False
    # when >0, forward (no-cache path) returns (hidden, lm_weight) and
    # training uses fused_loss_fn (F.fused_linear_cross_entropy)
    fused_loss_chunk: int = 0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=176,
                       num_layers=4, num_heads=4, num_kv_heads=2,
                       max_seq_len=128, **kw)


def llama_7b(**kw):
    return LlamaConfig(hidden_size=4096, intermediate_size=11008,
                       num_layers=32, num_heads=32, **kw)


def llama_13b(**kw):
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_layers=40, num_heads=40, **kw)


from .gpt import _sp_active, cached_attention


def _rope(q, k, theta: float, offset=None):
    """Apply rotary position embedding to q/k ([B, S, H, D]); `offset`
    shifts the absolute positions (decode with KV cache) — a scalar, or
    a [B] vector of per-row offsets (continuous-batching slots)."""
    def f(qv, kv, *off):
        D = qv.shape[-1]
        S = qv.shape[1]
        half = D // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        pos = jnp.arange(S, dtype=jnp.float32)
        if off:
            o = jnp.asarray(off[0], jnp.float32)
            if o.ndim == 1:                     # per-row -> [B, S]
                pos = pos[None, :] + o[:, None]
            else:
                pos = pos + o
        ang = pos[..., None] * freqs            # [S, half] or [B, S, half]
        if ang.ndim == 2:
            cos = jnp.cos(ang)[None, :, None, :]   # [1, S, 1, half]
            sin = jnp.sin(ang)[None, :, None, :]
        else:
            cos = jnp.cos(ang)[:, :, None, :]      # [B, S, 1, half]
            sin = jnp.sin(ang)[:, :, None, :]

        def rot(x):
            # interleaved-pairs convention: (x0, x1) -> (x0 c - x1 s,
            # x1 c + x0 s); computed in fp32, cast back
            xf = x.astype(jnp.float32)
            x0 = xf[..., 0::2]
            x1 = xf[..., 1::2]
            r0 = x0 * cos - x1 * sin
            r1 = x1 * cos + x0 * sin
            out = jnp.stack([r0, r1], axis=-1).reshape(x.shape)
            return out.astype(x.dtype)

        return rot(qv), rot(kv)

    if offset is not None:
        return apply(f, q, k, offset, _op_name="rope")
    return apply(f, q, k, _op_name="rope")


class LlamaAttention(Layer):
    """Causal self-attention with RoPE and GQA, TP-sharded heads."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, nh, nkv = cfg.hidden_size, cfg.num_heads, cfg.kv_heads
        if h % nh:
            raise ValueError("hidden_size % num_heads != 0")
        if nh % nkv:
            raise ValueError("num_heads % num_kv_heads != 0")
        self.num_heads = nh
        self.kv_heads = nkv
        self.head_dim = h // nh
        self.theta = cfg.rope_theta
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = ColumnParallelLinear(h, nh * self.head_dim,
                                           weight_attr=init, has_bias=False,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(h, nkv * self.head_dim,
                                           weight_attr=init, has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, nkv * self.head_dim,
                                           weight_attr=init, has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(nh * self.head_dim, h,
                                        weight_attr=init, has_bias=False,
                                        input_is_parallel=True)

    def forward(self, x, cache=None, pos=None):
        B, S, _ = x.shape
        hd, nh, nkv = self.head_dim, self.num_heads, self.kv_heads
        q = T.reshape(self.q_proj(x), [B, S, nh, hd])
        k = T.reshape(self.k_proj(x), [B, S, nkv, hd])
        v = T.reshape(self.v_proj(x), [B, S, nkv, hd])
        q, k = _rope(q, k, self.theta, offset=pos)
        if cache is not None:
            # caches keep nkv heads; cached_attention broadcasts for GQA
            ctx, kc, vc = cached_attention(q, k, v, cache[0], cache[1],
                                           pos)
            return self.o_proj(
                T.reshape(ctx, [B, S, nh * hd])), (kc, vc)
        if _sp_active():
            if nkv != nh:       # the ring schedule wants a key head a head
                rep = nh // nkv
                k = T.repeat_interleave(k, rep, axis=2)
                v = T.repeat_interleave(v, rep, axis=2)
            ctx = ring_attention(q, k, v, causal=True)
        else:
            ctx, _ = F.flash_attention(q, k, v, causal=True,
                                       training=self.training)
        return self.o_proj(T.reshape(ctx, [B, S, nh * hd]))


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        init = I.Normal(0.0, cfg.initializer_range)
        self.gate_proj = ColumnParallelLinear(h, m, weight_attr=init,
                                              has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, weight_attr=init,
                                            has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(m, h, weight_attr=init,
                                           has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(Layer):
    """Pre-RMSNorm block (the unit the pipeline stacks)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, cache=None, pos=None):
        if cache is not None:
            att, cache = self.self_attn(self.input_layernorm(x), cache,
                                        pos)
            x = x + att
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, cache
        x = x + self.self_attn(self.input_layernorm(x))
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        if cfg.scan_layers:
            from .scanned import ScannedStack
            self.blocks = ScannedStack(lambda: LlamaBlock(cfg),
                                       cfg.num_layers,
                                       cfg.initializer_range,
                                       recompute=cfg.recompute,
                                       recompute_policy=cfg.recompute_policy)
        else:
            self.blocks = []
            for i in range(cfg.num_layers):
                blk = LlamaBlock(cfg)
                self.add_sublayer(f"block_{i}", blk)
                self.blocks.append(blk)
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)

    def forward(self, ids, caches=None, pos=None):
        if ids.shape[-1] > self.cfg.max_seq_len:
            raise ValueError(
                f"sequence length {ids.shape[-1]} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")
        x = self.embed_tokens(ids)
        if caches is not None:
            if self.cfg.scan_layers:
                x, new_caches = self.blocks.forward_cached(x, caches, pos)
                return self.norm(x), new_caches
            new_caches = []
            for blk, c in zip(self.blocks, caches):
                x, c = blk(x, c, pos)
                new_caches.append(c)
            return self.norm(x), new_caches
        if self.cfg.scan_layers:
            return self.norm(self.blocks(x))
        if self.cfg.recompute and self.training:
            from ..distributed.recompute import recompute as _rc
            for blk in self.blocks:
                x = _rc(blk, x, policy=self.cfg.recompute_policy)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.llama = LlamaModel(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              weight_attr=I.Normal(
                                  0.0, cfg.initializer_range),
                              bias_attr=False)

    def forward(self, ids, caches=None, pos=None):
        if caches is not None:
            x, caches = self.llama(ids, caches, pos)
            return self.lm_head(x), caches
        x = self.llama(ids)
        if self.cfg.fused_loss_chunk and self.training:
            # training-perf contract: hand (hidden, lm_weight [H, V]) to
            # fused_loss_fn so the logits never materialize (gated on
            # self.training so eval() callers always get logits)
            return x, self.lm_head.weight
        return self.lm_head(x)

    def make_loss_fn(self):
        from .gpt import GPTForCausalLM
        return GPTForCausalLM.make_loss_fn(self)

    def new_cache(self, batch_size: int, max_len: int, dtype="bfloat16"):
        """Per-layer (k, v) caches [B, max_len, n_kv_heads, hd]; stacked
        (k_stack, v_stack) for scan_layers models; dtype "int8" selects
        the dynamically-quantized cache (quantized_kv_cache)."""
        from .generation import new_kv_caches
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        return new_kv_caches(cfg.num_layers, batch_size, max_len,
                             cfg.kv_heads, hd, dtype, cfg.scan_layers)

    def new_paged_cache(self, num_pages: int, page_size: int,
                        dtype="bfloat16"):
        """Per-layer (k, v) page pools for the paged serving engine
        (GQA: pools keep n_kv_heads; cached_attention broadcasts)."""
        from .generation import new_paged_kv_caches
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        return new_paged_kv_caches(cfg.num_layers, num_pages, page_size,
                                   cfg.kv_heads, hd, dtype,
                                   cfg.scan_layers)

    def generate(self, input_ids, max_new_tokens=32, **kw):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    # next-token shift identical to GPT's
    @staticmethod
    def loss_fn(logits, labels):
        from .gpt import GPTForCausalLM
        return GPTForCausalLM.loss_fn(logits, labels)

    @staticmethod
    def fused_loss_fn(outputs, labels, chunk_size=512):
        from .gpt import GPTForCausalLM
        return GPTForCausalLM.fused_loss_fn(outputs, labels,
                                            chunk_size=chunk_size)


class _EmbedStage(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.max_seq_len = cfg.max_seq_len
        self.embed = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))

    def forward(self, ids):
        if ids.shape[-1] > self.max_seq_len:
            raise ValueError(
                f"sequence length {ids.shape[-1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        return self.embed(ids)


class _HeadStage(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.head = Linear(cfg.hidden_size, cfg.vocab_size,
                           weight_attr=I.Normal(0.0, cfg.initializer_range),
                           bias_attr=False)

    def forward(self, x):
        return self.head(self.norm(x))


class LlamaPipelineForCausalLM(PipelineLayer):
    """LLaMA arranged for the in-program pipeline schedule (config 4)."""

    def __init__(self, cfg: LlamaConfig, num_stages: Optional[int] = None,
                 recompute_interval: int = 0,
                 num_micro: Optional[int] = None, interleave: int = 1):
        self.cfg = cfg
        super().__init__(
            layers=[LayerDesc(_EmbedStage, cfg)]
            + [LayerDesc(LlamaBlock, cfg) for _ in range(cfg.num_layers)]
            + [LayerDesc(_HeadStage, cfg)],
            num_stages=num_stages,
            loss_fn=LlamaForCausalLM.loss_fn,
            recompute_interval=recompute_interval,
            recompute_policy=cfg.recompute_policy,
            num_micro=num_micro, interleave=interleave)
