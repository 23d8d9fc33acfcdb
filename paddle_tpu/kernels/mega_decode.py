"""Mega-kernel decode inner step — cache read -> attention -> cache
write for one layer in ONE Pallas dispatch (ISSUE 19 tentpole,
prototype).

Reference role: fused_multi_transformer_op.cu (§2.4) fuses the whole
per-layer serving step into one CUDA op; MPK-style mega-kernelization
(PAPERS.md 2512.22219) makes the case for collapsing per-layer
launch + HBM round-trips. This kernel is the slot-engine S=1 decode
chain's analog: the three HBM round-trips per layer (read the written
cache for attention, materialize it again for the carry, copy the
donated buffer) become one — the cache streams through VMEM once,
attention runs against it plus the incoming row held in registers, and
the new row blends into the carry in place.

Dataflow (the part that moves the modeled bytes, not just the launch
count): attention reads the OLD cache under a STRICT ``< pos`` mask
and handles the new k/v row explicitly — exp(logit_new) and its value
contribution merge into the softmax normalizer directly — so the
written cache has exactly ONE consumer (the carry) and the write can
alias in place. The logits are broadcast-multiply-reduce over the head
dim (an S=1 decode step is a matrix-vector product — VPU-bound on
chip, and free of the layout-transpose duplication a dot would force
on the carry).

GQA: queries reshape to [nkv, groups, hd]; the cache is never
repeated.

One body, two wrappers: the math below (``_init_state`` /
``_online_update`` / ``cache_write._blend_rows``) is an online softmax over L-blocks of
the cache whose running (max, normalizer, context) state STARTS from
the incoming row — so a fully masked block contributes exp(-1e30 - m)
= 0 exactly. The TPU grid is (batch row, L-block) with the state in
VMEM scratch (a whole [1, L, nkv, hd] cache row per operand does not
fit fast memory at L=2048); ``interpret=True`` runs the same math
grid-free on CPU with the whole cache as its single block (a gridded
interpret kernel lowers to a while loop the hlo_cost model misprices).
Every intermediate keeps rank 4 — Mosaic cannot lay out the
rank-changing broadcasts/reshapes of the textbook formulation — so
queries arrive group-major ([B, g, nkv, hd], transposed outside the
kernel: one row per sequence, negligible).

Dispatch lives in nn/functional/flash_attention.py behind
``PADDLE_TPU_MEGA_DECODE``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .cache_write import _blend_rows

__all__ = ["mega_decode_step"]

_NEG_INF = -1e30


# cache rows one gridded program holds in VMEM: [1, _L_BLOCK, nkv, hd]
# is 0.5 MiB at nkv=16, hd=128 bf16 — two in + two out, double
# buffered, plus the f32 products, stays inside the scoped-VMEM default
_L_BLOCK = 128


def _init_state(qg, k, v, scale):
    """Online-softmax state seeded by the NEW row: qg [b,1,nkv,hd] (one
    query group), k/v [b,1,nkv,hd] f32. Returns (m, den, acc) with
    m/den [b,1,nkv,1], acc [b,1,nkv,hd]: exp(logit_new - m) = 1."""
    m = jnp.sum(k * qg, axis=-1, keepdims=True) * scale
    return m, jnp.ones_like(m), v


def _online_update(state, qg, kcb, vcb, pos, l0, scale):
    """Fold one OLD-cache block into the state. kcb/vcb [b,lb,nkv,hd]
    hold global positions l0..l0+lb; pos is a scalar or [b,1,1,1];
    attention is STRICT ``< pos`` (the new row is already in the
    state)."""
    m_old, den, acc = state
    kf, vf = kcb.astype(jnp.float32), vcb.astype(jnp.float32)
    logits = jnp.sum(kf * qg, axis=-1, keepdims=True) * scale
    l_ids = l0 + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(l_ids < pos, logits, _NEG_INF)    # [b,lb,nkv,1]
    m = jnp.maximum(m_old, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_old - m)
    p = jnp.exp(logits - m)
    den = den * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc = acc * alpha + jnp.sum(p * vf, axis=1, keepdims=True)
    return m, den, acc


def _kernel_whole(pos_ref, q_ref, k_ref, v_ref, kc_ref, vc_ref,
                  ctx_ref, kco_ref, vco_ref, *, scale):
    """Grid-free wrapper (interpret / CPU): the whole cache is one
    block, positions broadcast down the batch axis."""
    B = kc_ref.shape[0]
    pos = pos_ref[:].reshape(B, 1, 1, 1)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    kc, vc = kc_ref[...], vc_ref[...]
    for gi in range(q_ref.shape[1]):
        qg = q_ref[:, gi:gi + 1].astype(jnp.float32)
        st = _online_update(_init_state(qg, k, v, scale), qg, kc, vc,
                            pos, 0, scale)
        ctx_ref[:, gi:gi + 1] = (st[2] / st[1]).astype(ctx_ref.dtype)
    kco_ref[...] = _blend_rows(kc, k_ref[...], pos, 0)
    vco_ref[...] = _blend_rows(vc, v_ref[...], pos, 0)


def _kernel_block(pos_ref, q_ref, k_ref, v_ref, kc_ref, vc_ref,
                  ctx_ref, kco_ref, vco_ref, m_scr, den_scr, acc_scr,
                  *, scale):
    """Gridded wrapper (TPU): one program per (batch row, L-block), the
    state carried in VMEM scratch across the L axis."""
    b, j = pl.program_id(0), pl.program_id(1)
    nj = pl.num_programs(1)
    pos = pos_ref[b]
    l0 = j * kc_ref.shape[1]
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    kc, vc = kc_ref[...], vc_ref[...]
    for gi in range(q_ref.shape[1]):
        qg = q_ref[:, gi:gi + 1].astype(jnp.float32)

        @pl.when(j == 0)
        def _(gi=gi, qg=qg):
            m_scr[gi], den_scr[gi], acc_scr[gi] = _init_state(
                qg, k, v, scale)

        st = _online_update((m_scr[gi], den_scr[gi], acc_scr[gi]), qg,
                            kc, vc, pos, l0, scale)
        m_scr[gi], den_scr[gi], acc_scr[gi] = st

        @pl.when(j == nj - 1)
        def _(gi=gi, st=st):
            ctx_ref[:, gi:gi + 1] = (st[2] / st[1]).astype(ctx_ref.dtype)

    kco_ref[...] = _blend_rows(kc, k_ref[...], pos, l0)
    vco_ref[...] = _blend_rows(vc, v_ref[...], pos, l0)


def mega_decode_step(q, k, v, kc, vc, pos, *, interpret: bool = False,
                     gridded: bool | None = None):
    """One-dispatch S=1 decode layer step.

    q: [B, 1, nh, hd]; k/v: [B, 1, nkv, hd]; kc/vc: [B, L, nkv, hd]
    (plain array slot caches); pos: [B] int32. Returns
    (ctx [B, 1, nh, hd], kc', vc') with both caches aliased in place.
    Numerics: f32 accumulation; softmax reassociation drifts ~1e-7 vs
    the unfused chain (greedy tokens bit-identical on the registry
    fixture — PERF.md PR 19 documents the bound). ``gridded`` defaults
    to ``not interpret``; tests pass ``interpret=True, gridded=True``
    to run the chip's blocked form through the interpreter.
    """
    B, L, nkv, hd = kc.shape
    nh = q.shape[2]
    g = nh // nkv
    scale = 1.0 / float(hd) ** 0.5
    pos = jnp.asarray(pos, jnp.int32)
    # group-major queries: head kv*g + gi -> [gi, kv]
    q4 = q.reshape(B, nkv, g, hd).transpose(0, 2, 1, 3)
    out_shape = [
        jax.ShapeDtypeStruct(q4.shape, q.dtype),
        jax.ShapeDtypeStruct(kc.shape, kc.dtype),
        jax.ShapeDtypeStruct(vc.shape, vc.dtype),
    ]
    # operand indices count the scalar-prefetch arg: pos=0, q=1, k=2,
    # v=3, kc=4, vc=5 -> caches alias outputs 1 and 2
    aliases = {4: 1, 5: 2}
    if gridded is None:
        gridded = not interpret
    if not gridded:
        ctx4, kc2, vc2 = pl.pallas_call(
            functools.partial(_kernel_whole, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
                out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3),
            out_shape=out_shape,
            input_output_aliases=aliases,
            interpret=interpret,
        )(pos, q4, k, v, kc, vc)
    else:
        # a partial tail block would feed padded garbage into p * v
        lb = _L_BLOCK if L % _L_BLOCK == 0 else L
        qblk = (1, g, nkv, hd)
        rblk = (1, 1, nkv, hd)
        cblk = (1, lb, nkv, hd)
        row = lambda b, j, *_: (b, 0, 0, 0)  # noqa: E731
        blk = lambda b, j, *_: (b, j, 0, 0)  # noqa: E731
        ctx4, kc2, vc2 = pl.pallas_call(
            functools.partial(_kernel_block, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, pl.cdiv(L, lb)),
                in_specs=[pl.BlockSpec(qblk, row),
                          pl.BlockSpec(rblk, row),
                          pl.BlockSpec(rblk, row),
                          pl.BlockSpec(cblk, blk),
                          pl.BlockSpec(cblk, blk)],
                out_specs=[pl.BlockSpec(qblk, row),
                           pl.BlockSpec(cblk, blk),
                           pl.BlockSpec(cblk, blk)],
                scratch_shapes=[
                    pltpu.VMEM((g, 1, 1, nkv, 1), jnp.float32),
                    pltpu.VMEM((g, 1, 1, nkv, 1), jnp.float32),
                    pltpu.VMEM((g, 1, 1, nkv, hd), jnp.float32)]),
            out_shape=out_shape,
            input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(pos, q4, k, v, kc, vc)
    ctx = ctx4.transpose(0, 2, 1, 3).reshape(B, 1, nh, hd)
    return ctx, kc2, vc2
