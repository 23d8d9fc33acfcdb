"""SmallThinker decoder family (PowerInfer/SmallThinker-21BA3B-Instruct,
arXiv:2507.20984; 4B-A0.6B is the same code) for training.

A decoder of pre-norm blocks, two RMSNorms each, every layer sparse and
nothing dense beside the experts. Attention is grouped-query (28 query heads
on 4 key/value heads of 128: groups of 7) and of two kinds by the layer's
entries in ``sliding_window_layout`` and ``rope_layout``: a causal window of
``sliding_window_size`` keys with RoPE, or full causal attention with no
positions at all (NoPE). The feed-forward of every layer is a dropless
token-choice layer of ReLU-gated experts (``distributed.moe.TokenChoiceMoE``,
``down(relu(gate(x)) * up(x))``, no shared expert) whose ROUTER READS THE
BLOCK'S INPUT, the hidden state before the attention, while the experts read
the normed state after it: top-k of the raw logits, then a softmax over the
chosen ones (``moe_primary_router_apply_softmax``). Untied head.

The training path writes q [B, 28, S, 128] and k, v [B, 4, S, 128] in the
attention kernel's own layout from the projections (``_RotaryHeads``: q's
``head_dim ** -0.5`` on the float32 accumulator, RoPE in its half-split form
on the accumulator too where the layer has positions, one rounding) into
``head_major_attention(..., window=)`` and reads the context from it by one
contraction over (heads, head_dim) (``models/deepseek_v3.py``'s
``_HeadsIn``): nothing but the kernel touches the operands.

The router's choice depends on the block's input alone; the block asks for
it first (``TokenChoiceMoE.route``), the scope ``router`` is a child of the
block ahead of ``attn``, and XLA schedules the scores and the top-k before
the q projection (PERF.md section 6, PR 36). The dispatch's sort, positions
and sizes could be issued there too, but live inside the expert layer's
``lax.cond`` branch beside the grouped products, whose operand waits for
the attention.

Layers are unrolled (``models/scanned.py`` refuses blocks with buffers, and
the expert layer keeps three); ``recompute`` checkpoints each block, the
expert layer's counts coming out of the block as a value and its buffers
updated outside it, as ``models/afmoe.py``. One chip's share of expert
parallelism is a configuration: ``experts_held`` of the
``moe_num_primary_experts`` published, from ``expert_offset`` on.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax.numpy as jnp

from ..autograd.tape import apply
from ..distributed.meta_parallel import VocabParallelEmbedding
from ..distributed.meta_parallel.mp_layers import _constrain
from ..distributed.moe import TokenChoiceMoE
from ..nn import initializer as I
from ..nn import Linear, RMSNorm
from ..nn.functional.flash_attention import head_major_attention
from ..nn.layer_base import Layer
from .deepseek_v3 import _HeadsIn, _HeadsOut
from .gpt import GPTForCausalLM

__all__ = ["SmallThinkerConfig", "SmallThinkerAttention",
           "SmallThinkerBlock", "SmallThinkerModel",
           "SmallThinkerForCausalLM"]

_PERIOD = (0, 1, 1, 1)      # full NoPE, then three window layers with RoPE


@dataclass
class SmallThinkerConfig:
    """The defaults are PowerInfer/SmallThinker-21BA3B-Instruct's published
    config.json (21B-A3B), its keys under their own names."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    # one entry a layer; None: the published period (0, 1, 1, 1)
    rope_layout: Optional[Sequence[int]] = None
    sliding_window_layout: Optional[Sequence[int]] = None
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64       # published: the router's width
    experts_held: Optional[int] = None      # None: all of them live here
    expert_offset: int = 0
    moe_num_active_primary_experts: int = 6
    # true: top-k of the logits, then a softmax over the chosen; false: a
    # sigmoid of every logit, the chosen ones' normed by norm_topk_prob
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    initializer_range: float = 0.02
    max_position_embeddings: int = 16384
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

    def layouts(self) -> tuple:
        """((rope, window) a layer): whether it turns q and k by position,
        and whether it sees ``sliding_window_size`` keys only."""
        n = self.num_hidden_layers
        out = []
        for given in (self.rope_layout, self.sliding_window_layout):
            one = tuple(int(v) for v in given) if given is not None \
                else tuple(_PERIOD[i % len(_PERIOD)] for i in range(n))
            if len(one) != n or set(one) - {0, 1}:
                raise ValueError("rope_layout and sliding_window_layout "
                                 f"hold a 0 or 1 for each of {n} layers")
            out.append(one)
        return tuple(zip(*out))


class _RotaryHeads(_HeadsOut):
    """``models/deepseek_v3.py``'s ``_HeadsOut`` (x [B, S, in] times
    ``weight`` [in, heads * width] viewed [in, heads, width], column-parallel
    over "mp") for whole heads -> [B, heads, S, width], the attention
    kernel's own layout. On the float32 accumulator, before the one
    rounding: ``scale``, and with ``theta`` RoPE in the half-split form
    (column i of the first half pairs with column i of the second, turned
    by pos * theta^(-i / half))."""

    def forward(self, x, scale=1.0, theta=None):
        half = self.width // 2

        def project(xv, w):
            acc_t = jnp.promote_types(xv.dtype, jnp.float32)
            w = w.reshape(-1, self.heads, self.width)
            acc = jnp.einsum("bsk,khd->bhsd", xv, w.astype(xv.dtype),
                             preferred_element_type=acc_t) * scale
            if theta is not None:
                freqs = theta ** (-jnp.arange(half, dtype=jnp.float32)
                                  / half)
                ang = jnp.arange(xv.shape[1],
                                 dtype=jnp.float32)[:, None] * freqs
                cos, sin = jnp.cos(ang), jnp.sin(ang)       # [S, half]
                a, b = acc[..., :half], acc[..., half:]
                acc = jnp.concatenate([a * cos - b * sin,
                                       b * cos + a * sin], axis=-1)
            return acc.astype(xv.dtype)

        y = apply(project, x, self.weight, _op_name="linear")
        return _constrain(y, None, "mp", None, None)


class SmallThinkerAttention(Layer):
    """Grouped-query causal attention; ``rope``: q and k turned by position
    (else none at all); ``window``: the keys a query sees (None: all)."""

    def __init__(self, cfg: SmallThinkerConfig, rope: bool, window: bool):
        super().__init__()
        h, hd = cfg.hidden_size, cfg.head_dim
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        self.head_dim = hd
        self.theta = float(cfg.rope_theta) if rope else None
        self.window = int(cfg.sliding_window_size) if window else None
        init = I.Normal(0.0, cfg.initializer_range)
        self.q_proj = _RotaryHeads(h, nh, hd, init)
        self.k_proj = _RotaryHeads(h, nkv, hd, init)
        self.v_proj = _RotaryHeads(h, nkv, hd, init)
        self.o_proj = _HeadsIn(nh, hd, h, init)

    def forward(self, x):
        q = self.q_proj(x, self.head_dim ** -0.5, self.theta)
        k = self.k_proj(x, theta=self.theta)
        v = self.v_proj(x)
        return self.o_proj(head_major_attention(q, k, v, causal=True,
                                                window=self.window))


class SmallThinkerBlock(Layer):
    """One layer. Returns ``(x, counts)``, the counts of tokens by
    published expert, so that it can be recomputed."""

    def __init__(self, cfg: SmallThinkerConfig, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        rope, window = cfg.layouts()[index]
        self.input_layernorm = RMSNorm(h, eps)
        self.attn = SmallThinkerAttention(cfg, bool(rope), bool(window))
        self.post_attention_layernorm = RMSNorm(h, eps)
        self.mlp = TokenChoiceMoE(
            h, cfg.moe_ffn_hidden_size, cfg.moe_num_primary_experts,
            cfg.moe_num_active_primary_experts,
            experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
            route_norm=cfg.norm_topk_prob, bias_update_rate=0.0,
            initializer_range=cfg.initializer_range,
            score="softmax_of_chosen"
            if cfg.moe_primary_router_apply_softmax else "sigmoid",
            activation="relu")

    def forward(self, x):
        # the router reads x as it enters the block: its choice, and with
        # it the dispatch's sort and sizes, waits for no attention
        routing = self.mlp.route(x)
        x = x + self.attn(self.input_layernorm(x))
        y, counts = self.mlp(self.post_attention_layernorm(x),
                             routing=routing)
        return x + y, counts


class SmallThinkerModel(Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.blocks = []
        for i in range(cfg.num_hidden_layers):
            blk = SmallThinkerBlock(cfg, i)
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
            x, counts = _rc(blk, x, policy=cfg.recompute_policy) if remat \
                else blk(x)
            if self.training:           # outside the recomputed region
                blk.mlp.note_load(counts)
        return self.norm(x)


class SmallThinkerForCausalLM(Layer):
    def __init__(self, cfg: SmallThinkerConfig):
        super().__init__()
        self.cfg = cfg
        self.model = SmallThinkerModel(cfg)
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
