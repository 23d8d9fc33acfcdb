"""GPT decoder family — the flagship pretraining model.

Capability parity: the reference trains GPT-3-scale models through Fleet
hybrid parallelism (SURVEY.md §3.4 north-star path; model code lives in
PaddleNLP, driven by the fleet TP layers mpu/mp_layers.py and
PipelineLayer). This is a TPU-first implementation of the same model
family, wired for every mesh axis at once:

- mp: qkv/mlp-in are ColumnParallelLinear, out-proj/mlp-out are
  RowParallelLinear, embeddings are VocabParallel (one GSPMD allreduce per
  block pair, Megatron layout over the innermost ICI axis);
- sp: attention dispatches to ring_attention when the "sp" axis is real
  (exceeds the reference — it has no sequence parallelism, §5.7);
- pp: GPTPipelineForCausalLM arranges the same blocks as a PipelineLayer
  (stacked params, in-program microbatch ring schedule);
- dp/sharding: batch sharding + ZeRO slot sharding come from
  ParallelTrainStep, orthogonal to the model.

All matmul-heavy compute is bfloat16-friendly (use amp.auto_cast or
Layer.bfloat16()); attention/log-softmax accumulate in fp32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .. import tensor as T
from ..autograd.tape import apply
from ..core.tensor import Tensor
from ..jit.functional import functional_call
from ..distributed import mesh as mesh_mod
from ..distributed.meta_parallel import (ColumnParallelLinear, LayerDesc,
                                         PipelineLayer, RowParallelLinear,
                                         VocabParallelEmbedding)
from ..distributed.meta_parallel.mp_layers import _constrain
from ..distributed.sequence_parallel import ring_attention
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn import Dropout, Embedding, LayerNorm, Linear
from .scanned import ScannedStack

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPipelineForCausalLM", "gpt_tiny", "gpt_125m", "gpt_1p3b",
           "gpt_6p7b"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_mult: int = 4
    dropout: float = 0.0
    tie_embeddings: bool = True
    use_moe: bool = False
    moe_experts: int = 8
    initializer_range: float = 0.02
    # rematerialize each block's activations in backward (jax.checkpoint;
    # parity: fleet recompute_interval=1 over the decoder stack)
    recompute: bool = False
    # what a recomputed block keeps beside its input: "full" the attention
    # kernel's result and logsumexp, "dots" matmul outputs too; a
    # jax.checkpoint_policies callable (nothing_saveable: keep nothing)
    # passes through (distributed/recompute.py)
    recompute_policy: str = "full"
    # compile the block stack as ONE lax.scan over [L, ...]-stacked params
    # instead of L unrolled copies — O(1) HLO in depth (GPTScannedBlocks)
    scan_layers: bool = False
    # when >0, forward (no-cache path) returns (hidden, lm_weight) instead
    # of logits and training uses fused_loss_fn — the LM-head projection
    # streams through F.fused_linear_cross_entropy in chunks of this many
    # tokens, so the [tokens, vocab] logits never materialize in HBM
    fused_loss_chunk: int = 0


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                     num_heads=4, max_seq_len=128, **kw)


def gpt_125m(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def gpt_6p7b(**kw):
    return GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                     max_seq_len=2048, **kw)


def _sp_active() -> bool:
    mesh = mesh_mod.get_mesh(create_default=False)
    return mesh is not None and mesh.shape.get("sp", 1) > 1


# re-export: incremental-decode attention now lives beside the flash
# kernel (generic serving infrastructure, not GPT-specific)
from ..nn.functional.flash_attention import (  # noqa: E402
    cached_attention, head_major_attention)
from .generation import new_kv_caches as _new_cache  # noqa: E402


def _split3(t):
    """The q, k and v thirds of a fused last axis [..., 3H], laid out
    [q(H); k(H); v(H)]: of ``qkv.weight`` [H, 3H], ``qkv.bias`` [3H] and
    the fused product [B, S, 3H] alike."""
    H = t.shape[-1] // 3
    return t[..., :H], t[..., H:2 * H], t[..., 2 * H:]


class GPTAttention(Layer):
    """Causal self-attention, TP-sharded heads, sp-aware dispatch.

    Training (no cache, no real "sp" axis): the projections write and read
    the attention kernel's own [B, nh, S, hd] layout, so nothing but the
    matmuls and the kernel touches q, k, v, the context or their
    cotangents (``_qkv_heads``, ``_out_heads``). Serving and ring
    attention keep [B, S, nh, hd] rows from the one fused product
    (``_qkv``). Both read the same ``qkv.weight`` [H, 3H], ``qkv.bias``
    [3H] and ``out_proj.weight`` [H, H]."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, nh = cfg.hidden_size, cfg.num_heads
        if h % nh:
            raise ValueError("hidden_size % num_heads != 0")
        self.num_heads = nh
        self.head_dim = h // nh
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv = ColumnParallelLinear(h, 3 * h, weight_attr=init,
                                        gather_output=False)
        self.out_proj = RowParallelLinear(h, h, weight_attr=init,
                                          input_is_parallel=True)
        self.dropout = Dropout(cfg.dropout)

    def _qkv(self, x):
        B, S, _ = x.shape
        nh, hd = self.num_heads, self.head_dim

        def rows(qkv):                          # [B, S, 3H] (mp-sharded)
            return tuple(t.reshape(B, S, nh, hd) for t in _split3(qkv))

        return apply(rows, self.qkv(x), _op_name="split_qkv")

    def _qkv_heads(self, x):
        """q, k, v as [B, nh, S, hd]: each a product of x with its third
        of ``qkv.weight`` viewed [H, nh, hd], the bias and (q's) the
        softmax scale put on the f32 accumulator before the one rounding.
        Column-parallel as ``qkv`` itself: heads sharded over "mp"."""
        nh, hd = self.num_heads, self.head_dim

        def project(x, w, b):
            acc_t = jnp.promote_types(x.dtype, jnp.float32)
            out = []
            # a product that writes [b, h, s, d] wants its weight with the
            # contracted dimension minor. Left alone, the compiler gives that
            # layout to the whole stacked [L, H, 3H] operand of a scanned
            # stack and keeps a second copy of it through the step (0.53 GiB
            # more at the peak of train-gpt-1.3b, and slower: PERF.md section
            # 6, PR 33); pinned as stored, a layer's thirds are transposed
            # where they are used
            w = with_layout_constraint(w, Layout(major_to_minor=(0, 1)))
            for wi, bi, scale in zip(_split3(w), _split3(b),
                                     (hd ** -0.5, 1.0, 1.0)):
                acc = jnp.einsum("bsk,khd->bhsd", x, wi.reshape(-1, nh, hd),
                                 preferred_element_type=acc_t)
                acc = (acc + bi.reshape(nh, 1, hd).astype(acc_t)) * scale
                out.append(acc.astype(x.dtype))
            return tuple(out)

        with jax.named_scope(self.qkv._scope_name):
            qkv = apply(project, x, self.qkv.weight, self.qkv.bias,
                        _op_name="linear")
            return tuple(_constrain(t, None, "mp", None, None) for t in qkv)

    def _out_heads(self, ctx):
        """``out_proj`` of a context [B, nh, S, hd]: one contraction over
        (nh, hd) with its weight viewed [nh, hd, H]. Row-parallel as
        ``out_proj`` itself: partial products reduced over "mp" (exactly:
        the quantized wire is the serving programs'), bias added once,
        after."""
        nh, hd = self.num_heads, self.head_dim
        with jax.named_scope(self.out_proj._scope_name):
            y = apply(lambda c, w: jnp.einsum("bhsd,hdk->bsk", c,
                                              w.reshape(nh, hd, -1)),
                      ctx, self.out_proj.weight, _op_name="linear")
            return _constrain(y, None, None, None) + self.out_proj.bias

    def forward(self, x, cache=None, pos=None):
        B, S, H = x.shape
        if cache is None and not _sp_active():
            ctx = head_major_attention(*self._qkv_heads(x), causal=True)
            return self.dropout(self._out_heads(ctx))
        q, k, v = self._qkv(x)
        if cache is not None:
            ctx, kc, vc = cached_attention(q, k, v, cache[0], cache[1],
                                           pos)
            return self.dropout(self.out_proj(
                T.reshape(ctx, [B, S, H]))), (kc, vc)
        ctx = ring_attention(q, k, v, causal=True)
        return self.dropout(self.out_proj(T.reshape(ctx, [B, S, H])))


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        init = I.Normal(0.0, cfg.initializer_range)
        self.fc_in = ColumnParallelLinear(h, cfg.ffn_mult * h,
                                          weight_attr=init,
                                          gather_output=False)
        self.fc_out = RowParallelLinear(cfg.ffn_mult * h, h,
                                        weight_attr=init,
                                        input_is_parallel=True)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x))))


class GPTBlock(Layer):
    """Pre-LN transformer block (the unit the pipeline stacks)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln_2 = LayerNorm(cfg.hidden_size)
        if cfg.use_moe:
            from ..distributed.moe import MoELayer
            self.mlp = MoELayer(cfg.hidden_size,
                                cfg.ffn_mult * cfg.hidden_size,
                                cfg.moe_experts)
        else:
            self.mlp = GPTMLP(cfg)

    def forward(self, x, cache=None, pos=None):
        if cache is not None:
            att, cache = self.attn(self.ln_1(x), cache, pos)
            x = x + att
            x = x + self.mlp(self.ln_2(x))
            return x, cache
        x = x + self.attn(self.ln_1(x))
        x = x + self.mlp(self.ln_2(x))
        return x


class GPTScannedBlocks(ScannedStack):
    """GPT decoder stack as one lax.scan (``cfg.scan_layers``) — see
    models/scanned.py for the full design. MoE blocks work (per-layer
    aux losses ride the scan outputs); dropout is rejected (traced-once
    body would reuse one RNG draw per layer)."""

    def __init__(self, cfg: GPTConfig):
        ScannedStack.reject_dropout(cfg.dropout)
        super().__init__(lambda: GPTBlock(cfg), cfg.num_layers,
                         cfg.initializer_range, recompute=cfg.recompute,
                         recompute_policy=cfg.recompute_policy)
        self.cfg = cfg


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        self.position_embeddings = Embedding(
            cfg.max_seq_len, cfg.hidden_size, weight_attr=init)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, ids, pos=None):
        S = ids.shape[-1]
        max_len = self.position_embeddings.num_embeddings
        if S > max_len:
            raise ValueError(
                f"sequence length {S} exceeds max_seq_len {max_len}")
        positions = T.arange(0, S, dtype="int64")
        if pos is not None:                     # decode offset
            p = T.cast(pos, "int64")
            if len(tuple(p.shape)) == 1:
                # per-row offsets [B] (continuous-batching slots, each
                # at its own decode position) -> positions [B, S]
                positions = (T.reshape(positions, [1, S])
                             + T.reshape(p, [-1, 1]))
            else:
                positions = positions + p
        x = self.word_embeddings(ids) + self.position_embeddings(positions)
        return self.dropout(x)


class GPTModel(Layer):
    """Decoder stack without head. Parity role: GPTModel in the reference
    ecosystem driven through fleet (SURVEY.md §3.4)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        if cfg.scan_layers:
            self.blocks = GPTScannedBlocks(cfg)
        else:
            self.blocks = []
            for i in range(cfg.num_layers):
                blk = GPTBlock(cfg)
                self.add_sublayer(f"block_{i}", blk)
                self.blocks.append(blk)
        self.ln_f = LayerNorm(cfg.hidden_size)

    def forward(self, ids, caches=None, pos=None):
        if caches is not None:
            x = self.embeddings(ids, pos)
            if self.cfg.scan_layers:
                x, new_caches = self.blocks.forward_cached(x, caches, pos)
                return self.ln_f(x), new_caches
            new_caches = []
            for blk, c in zip(self.blocks, caches):
                x, c = blk(x, c, pos)
                new_caches.append(c)
            return self.ln_f(x), new_caches
        x = self.embeddings(ids)
        if self.cfg.scan_layers:
            return self.ln_f(self.blocks(x))
        if self.cfg.recompute and self.training:
            if self.cfg.use_moe:
                raise NotImplementedError(
                    "cfg.recompute with use_moe: MoELayer's aux-loss side "
                    "channel would cross the jax.checkpoint boundary "
                    "(tracer leak). distributed.moe.TokenChoiceMoE hands "
                    "its counts out as values and runs under recomputation "
                    "(models/afmoe.py); for MoELayer use "
                    "GPTPipelineForCausalLM's recompute_interval")
            from ..distributed.recompute import recompute as _rc
            for blk in self.blocks:
                x = _rc(blk, x, policy=self.cfg.recompute_policy)
        else:
            for blk in self.blocks:
                x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    """LM head on top; loss = causal LM cross-entropy.

    lm head is tied to the (vocab-parallel) embedding when
    cfg.tie_embeddings — the sharded logits matmul then feeds the
    ParallelCrossEntropy-style fp32 softmax inside F.cross_entropy.
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  weight_attr=I.Normal(
                                      0.0, cfg.initializer_range),
                                  bias_attr=False)

    def forward(self, ids, caches=None, pos=None):
        if caches is not None:
            x, caches = self.gpt(ids, caches, pos)
            return self._logits(x), caches
        x = self.gpt(ids)
        if self.cfg.fused_loss_chunk and self.training:
            # training-perf contract (cfg.fused_loss_chunk): hand the
            # hidden states + LM weight to fused_loss_fn so the logits
            # never materialize. Gated on self.training so eval()/
            # perplexity callers always get logits; decode/caches path
            # above returns logits for generate() either way.
            return x, self._lm_weight()
        return self._logits(x)

    def _lm_weight(self):
        if self.cfg.tie_embeddings:
            return self.gpt.embeddings.word_embeddings.weight  # [V, H]
        return self.lm_head.weight                             # [H, V]

    def _logits(self, x):
        if self.cfg.tie_embeddings:
            w = self.gpt.embeddings.word_embeddings.weight
            return T.matmul(x, T.transpose(w, [1, 0]))
        return self.lm_head(x)

    def new_cache(self, batch_size: int, max_len: int, dtype="bfloat16"):
        """Per-layer (k, v) cache arrays [B, max_len, nh, hd] for
        generate()."""
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        return _new_cache(cfg.num_layers, batch_size, max_len,
                          cfg.num_heads, hd, dtype, cfg.scan_layers)

    def new_paged_cache(self, num_pages: int, page_size: int,
                        dtype="bfloat16"):
        """Per-layer (k, v) page POOLS for the paged serving engine —
        [num_pages, page_size, nh, hd] each; block tables are engine
        state, not part of this pytree."""
        from .generation import new_paged_kv_caches
        cfg = self.cfg
        hd = cfg.hidden_size // cfg.num_heads
        return new_paged_kv_caches(cfg.num_layers, num_pages, page_size,
                                   cfg.num_heads, hd, dtype,
                                   cfg.scan_layers)

    def generate(self, input_ids, max_new_tokens=32, **kw):
        from .generation import generate
        return generate(self, input_ids, max_new_tokens, **kw)

    @staticmethod
    def loss_fn(logits, labels):
        """Next-token prediction: logits at position i predict labels[i+1]
        (callers pass labels=input_ids; the shift happens here)."""
        V = logits.shape[-1]
        shifted_logits = T.slice(logits, [1], [0], [logits.shape[1] - 1])
        shifted_labels = T.slice(labels, [1], [1], [labels.shape[1]])
        return T.mean(F.cross_entropy(
            T.reshape(shifted_logits, [-1, V]),
            T.reshape(shifted_labels, [-1])))

    def make_loss_fn(self):
        """The loss composition this config trains with: fused_loss_fn
        bound to cfg.fused_loss_chunk when set, else plain loss_fn —
        call sites never re-encode the contract."""
        if self.cfg.fused_loss_chunk:
            import functools
            return functools.partial(self.fused_loss_fn,
                                     chunk_size=self.cfg.fused_loss_chunk)
        return self.loss_fn

    @staticmethod
    def fused_loss_fn(outputs, labels, chunk_size=512):
        """loss_fn counterpart for cfg.fused_loss_chunk models: outputs is
        (hidden, lm_weight) from a training-mode forward; the shifted
        tokens stream through F.fused_linear_cross_entropy so
        [tokens, vocab] logits never materialize.

        An eval()-mode forward returns plain logits (the fused return is
        gated on self.training), so make_loss_fn's output stays correct
        in both modes: logits fall through to loss_fn here."""
        if not isinstance(outputs, tuple):
            return GPTForCausalLM.loss_fn(outputs, labels)
        hidden, w = outputs
        S = hidden.shape[1]
        # no Layer stands here: named as the trainers name a loss
        with jax.named_scope("head_loss"):
            h_s = T.slice(hidden, [1], [0], [S - 1])
            l_s = T.slice(labels, [1], [1], [S])
            return F.fused_linear_cross_entropy(h_s, w, l_s,
                                                chunk_size=chunk_size)


class _EmbedStage(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.emb = GPTEmbeddings(cfg)

    def forward(self, ids):
        return self.emb(ids)


class _HeadStage(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.ln_f = LayerNorm(cfg.hidden_size)
        self.head = Linear(cfg.hidden_size, cfg.vocab_size,
                           weight_attr=I.Normal(0.0, cfg.initializer_range),
                           bias_attr=False)

    def forward(self, x):
        return self.head(self.ln_f(x))


class GPTPipelineForCausalLM(PipelineLayer):
    """The same GPT arranged for pipeline parallelism.

    Parity: PipelineLayer GPT arrangements in the reference test suite
    (unittests/collective/fleet/hybrid_parallel_pp_transformer.py). Blocks
    stack over the pp axis; embeddings/head run as prologue/epilogue (so
    tying across stages is not used here — reference PP GPT uses
    SharedLayerDesc; with one global program the head stays a separate
    Linear for homogeneity).
    """

    def __init__(self, cfg: GPTConfig, num_stages: Optional[int] = None,
                 recompute_interval: int = 0,
                 num_micro: Optional[int] = None, interleave: int = 1):
        self.cfg = cfg
        super().__init__(
            layers=[LayerDesc(_EmbedStage, cfg)]
            + [LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)]
            + [LayerDesc(_HeadStage, cfg)],
            num_stages=num_stages,
            loss_fn=GPTForCausalLM.loss_fn,
            recompute_interval=recompute_interval,
            recompute_policy=cfg.recompute_policy,
            num_micro=num_micro, interleave=interleave)
