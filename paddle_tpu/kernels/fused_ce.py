"""Fused softmax cross-entropy kernels — logits -> loss + dlogits with
an online log-sum-exp over the vocab axis (ISSUE 19 tentpole).

Reference role: paddle/phi/kernels/gpu/cross_entropy_kernel.cu (the
fused softmax-with-CE kernels). The naive composition materializes the
[N, V] softmax and one-hot; the forward here is ONE streaming pass per
row — the (max, sum-exp) pair carried through the classic logsumexp
monoid

    (m1, s1) + (m2, s2) = (M, s1*exp(m1-M) + s2*exp(m2-M)),
    M = max(m1, m2)

— and the backward is one streaming pass emitting
``dlogits = (exp(logits - lse) - onehot) * g`` with the one-hot
compare folded into the elementwise epilogue (never materialized).

Three entry points:

- ``ce_fwd`` / ``ce_bwd``: the Pallas kernels. The grid is (row-blocks,
  vocab-blocks) with the monoid carried in VMEM scratch across the
  vocab axis; ``interpret=True`` runs the same gridded bodies through
  the interpreter (one body: what the tests run is what the chip
  compiles).
- ``online_lse``: the monoid as ONE variadic ``lax.reduce`` — the
  kernel's dataflow expressed for XLA. This is what the CPU dispatch
  path (nn/functional/loss.py, ``PADDLE_TPU_FUSED_CE``) uses: on this
  backend XLA compiles it to a single pass over the logits (measured:
  the separate max pass and the materialized exp of the unfused chain
  both disappear), which keeps the modeled train-step inventory honest
  about what the Mosaic kernel does on-chip.

Padded-vocab tails: ``valid_vocab`` masks columns >= the real vocab out
of both the LSE and the backward (padded logits contribute exactly
zero probability), so models padding V up to a lane multiple lose
nothing. bf16 logits compute in f32 in-kernel and emit bf16 dlogits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ce_fwd", "ce_bwd", "online_lse"]

_NEG_INF = float("-inf")


# --------------------------------------------------- XLA (dispatch) form

def online_lse(lg, valid_vocab=None):
    """Row log-sum-exp in ONE pass: variadic reduce carrying the
    (running max, running scaled sum) logsumexp monoid. lg: [..., V]
    any float dtype; f32 result."""
    lg = lg.astype(jnp.float32)
    if valid_vocab is not None and valid_vocab != lg.shape[-1]:
        ids = lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
        lg = jnp.where(ids < valid_vocab, lg, _NEG_INF)

    def comb(a, b):
        m1, s1 = a
        m2, s2 = b
        m = jnp.maximum(m1, m2)
        # exp(-inf - -inf) = exp(nan) guard: reduce order is
        # unspecified, so a tree/vectorized reduction may pair two
        # padded lanes (m1 == m2 == -inf) even when the row has valid
        # columns — the select forces that operand's weight to exactly
        # 0 before the nan can reach s. (minimum(nan, 0) is nan, so
        # clamping the exponent does NOT work.) A finite m_i needs no
        # clamp: m_i - m <= 0 by construction.
        w1 = jnp.where(m1 == _NEG_INF, 0.0, jnp.exp(m1 - m))
        w2 = jnp.where(m2 == _NEG_INF, 0.0, jnp.exp(m2 - m))
        return m, s1 * w1 + s2 * w2

    m, s = lax.reduce((lg, jnp.ones_like(lg)),
                      (jnp.float32(_NEG_INF), jnp.float32(0.0)),
                      comb, (lg.ndim - 1,))
    return jnp.log(s) + m


# ------------------------------------------------------- Pallas kernels
#
# Per-row vectors (labels, per-row loss, lse, upstream grad) travel as
# [N, 1] columns with (bn, 1) blocks: the chip's compiler refuses rank-1
# (bn,) blocks (XLA tiles a rank-1 s32[N] operand T(1024), Mosaic
# T(128)), and every row reduction below keeps its dim so no value ever
# changes rank in-kernel.

def _fwd_kernel(labels_ref, lg_ref, per_ref, lse_ref, m_scr, s_scr,
                g_scr, *, valid_vocab, block_v):
    """One program per (row-block, vocab-block): the monoid carried in
    VMEM scratch across the vocab grid axis. labels_ref is the [bn, 1]
    row-block of labels."""
    iv, nv = pl.program_id(1), pl.num_programs(1)

    @pl.when(iv == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        g_scr[...] = jnp.zeros_like(g_scr)

    lg = lg_ref[...].astype(jnp.float32)                 # [bn, bv]
    col = iv * block_v + lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    lg = jnp.where(col < valid_vocab, lg, _NEG_INF)
    m_blk = jnp.max(lg, axis=-1, keepdims=True)          # [bn, 1]
    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, m_blk)
    # -inf - -inf guards (same as online_lse's comb): a row whose
    # running max is still -inf (all columns masked so far) must carry
    # s = 0 exactly, not 0 * exp(nan) = nan
    scale = jnp.where(m_old == _NEG_INF, 0.0, jnp.exp(m_old - m_new))
    s_blk = jnp.where(
        m_new == _NEG_INF, 0.0,
        jnp.sum(jnp.exp(lg - m_new), axis=-1, keepdims=True))
    m_scr[...] = m_new
    s_scr[...] = s_scr[...] * scale + s_blk
    hit = col == labels_ref[...]                         # [bn, bv]
    g_scr[...] = g_scr[...] + jnp.sum(jnp.where(hit, lg, 0.0), axis=-1,
                                      keepdims=True)

    @pl.when(iv == nv - 1)
    def _():
        lse = jnp.log(s_scr[...]) + m_scr[...]
        per_ref[...] = lse - g_scr[...]
        lse_ref[...] = lse


def _bwd_kernel(labels_ref, lg_ref, lse_ref, g_ref, dlg_ref, *,
                valid_vocab, block_v):
    """labels_ref / lse_ref / g_ref are [bn, 1] row-blocks."""
    iv = pl.program_id(1)
    lg = lg_ref[...].astype(jnp.float32)                 # [bn, bv]
    col = iv * block_v + lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    p = jnp.exp(lg - lse_ref[...])
    p = jnp.where(col < valid_vocab, p, 0.0)
    onehot = (col == labels_ref[...]).astype(jnp.float32)
    dlg_ref[...] = ((p - onehot) * g_ref[...]).astype(dlg_ref.dtype)


def ce_fwd(lg, labels, valid_vocab=None, *, block_n: int = 128,
           block_v: int = 512, interpret: bool = False):
    """Fused CE forward: per-row loss + LSE residual, one streaming
    pass. lg: [N, V]; labels: [N] int; returns (per [N] f32, lse [N]
    f32). ``interpret=True`` runs the SAME gridded body through the
    interpreter (tests; the CPU dispatch uses ``online_lse`` instead)."""
    N, V = lg.shape
    vv = V if valid_vocab is None else int(valid_vocab)
    labels = jnp.asarray(labels, jnp.int32).reshape(N, 1)
    bn, bv = min(block_n, N), min(block_v, V)
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    per, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, valid_vocab=vv, block_v=bv),
        grid=(pl.cdiv(N, bn), pl.cdiv(V, bv)),
        in_specs=[row, pl.BlockSpec((bn, bv), lambda i, j: (i, j))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((N, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((bn, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(labels, lg)
    return per[:, 0], lse[:, 0]


def ce_bwd(lg, labels, lse, g, valid_vocab=None, *, block_n: int = 128,
           block_v: int = 512, interpret: bool = False):
    """Fused CE backward: dlogits = (softmax - onehot) * g in one
    streaming pass (one-hot folded into the epilogue). Returns dlogits
    at lg's dtype. ``interpret`` as in ce_fwd."""
    N, V = lg.shape
    vv = V if valid_vocab is None else int(valid_vocab)
    labels = jnp.asarray(labels, jnp.int32).reshape(N, 1)
    lse = jnp.asarray(lse, jnp.float32).reshape(N, 1)
    g = jnp.asarray(g, jnp.float32).reshape(N, 1)
    bn, bv = min(block_n, N), min(block_v, V)
    row = pl.BlockSpec((bn, 1), lambda i, j: (i, 0))
    tile = pl.BlockSpec((bn, bv), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, valid_vocab=vv, block_v=bv),
        grid=(pl.cdiv(N, bn), pl.cdiv(V, bv)),
        in_specs=[row, tile, row, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((N, V), lg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(labels, lg, lse, g)
