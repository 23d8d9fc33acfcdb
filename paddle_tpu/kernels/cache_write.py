"""Fused KV-cache write kernels — the decode tick's 3-kernel one-hot
chains collapsed to one Pallas dispatch each (ISSUE 19 tentpole).

Reference role: fused_multi_transformer_op.cu's CacheKV write (§2.4 of
the paper) — the reference fuses the cache append into its mega
transformer op; here each masked write chain (one-hot build -> mask
broadcast -> select, three XLA kernels per cache array per micro-step)
becomes ONE kernel that computes the write mask on the fly and blends
the new rows into the cache block in VMEM.

Two forms, matching nn/functional/flash_attention.py's write paths:

- ``fused_slot_write``: the S=1 per-row slot-cache hot path (dense
  [B, L, nkv, hd] caches, one new row per sequence at its own
  position). TPU grid is one program per batch row; the interpret path
  is grid-free (whole-array block) — a gridded interpret kernel lowers
  to a dynamic-slice while loop whose body the hlo_cost model charges
  at FULL operand scale per trip, which would misprice the very chain
  this kernel exists to shrink.
- ``fused_paged_write``: the paged-pool form (page-indexed positions
  through a block table). TPU grid is one program per POOL PAGE — each
  physical page is visited by exactly one program instance, so the
  in-place pool update has no cross-program aliasing hazard; the
  candidate scan inside is a fori over the B*S incoming rows.

Both alias the cache operand to the output (donation preserved: the
pool updates in place, no second pool allocation). Quantization of
int8 rows stays with the caller (nn/functional/flash_attention.py owns
the cache dtype contract); these kernels blend pre-quantized payloads.

Dispatch gates live next to the functionals (flash_attention.py,
behind ``PADDLE_TPU_FUSED_CACHE_WRITE``); kernels here are pure
jittable functions, flash_block.py precedent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_slot_write", "fused_paged_write"]


# ------------------------------------------------------------ slot form

# rows of L one gridded program holds in VMEM (cache block
# [1, _L_BLOCK, nkv, hd]: 1 MiB at nkv=16, hd=128 bf16; in + out, double
# buffered = 4 MiB, inside every TPU generation's scoped-VMEM default)
_L_BLOCK = 256


def _blend_rows(cache, rows, pos, l0):
    """The one slot-write body: blend ``rows`` ([b, 1, ...]) into the
    ``cache`` block ([b, l, ...], holding global positions l0..l0+l) at
    position ``pos`` (scalar, or [b, 1, ...] broadcastable). The hit
    mask is a FULL-RANK iota compare — Mosaic cannot lay out a
    rank-changing ``hit[..., None, None]`` broadcast, and int8 blocks
    select in int32 (no packed-int8 select on the VPU)."""
    hit = l0 + lax.broadcasted_iota(jnp.int32, cache.shape, 1) == pos
    if cache.dtype == jnp.int8:
        return jnp.where(hit, rows.astype(jnp.int32),
                         cache.astype(jnp.int32)).astype(jnp.int8)
    return jnp.where(hit, rows.astype(cache.dtype), cache)


def _slot_kernel_whole(pos_ref, cache_ref, rows_ref, out_ref):
    """Grid-free wrapper (interpret / CPU): the whole array is one
    block, every row's position broadcast down the batch axis."""
    nd = len(cache_ref.shape)
    pos = pos_ref[:].reshape((cache_ref.shape[0],) + (1,) * (nd - 1))
    out_ref[...] = _blend_rows(cache_ref[...], rows_ref[...], pos, 0)


def _slot_kernel_block(pos_ref, cache_ref, rows_ref, out_ref):
    """Gridded wrapper (TPU): one program per (batch row, L-block); the
    block [1, lb, ...] sits in VMEM, pos[b] is an SMEM scalar."""
    b, j = pl.program_id(0), pl.program_id(1)
    out_ref[...] = _blend_rows(cache_ref[...], rows_ref[...], pos_ref[b],
                               j * cache_ref.shape[1])


def fused_slot_write(cache, rows, pos, *, interpret: bool = False,
                     gridded: bool | None = None):
    """One-kernel S=1 slot-cache write: ``cache[b, pos[b]] = rows[b, 0]``.

    cache: [B, L, ...] (the [B, L, nkv, hd] data array, or the
    [B, L, nkv] int8-cache scale plane); rows: [B, 1, ...] matching;
    pos: [B] int32. The cache operand is aliased to the output
    (in-place blend — donation flows through). ``gridded`` defaults to
    ``not interpret``; tests pass ``interpret=True, gridded=True`` to
    run the chip's blocked form through the interpreter.
    """
    B, L = cache.shape[0], cache.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if gridded is None:
        gridded = not interpret
    if not gridded:
        grid = ()
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        out_specs = pl.BlockSpec(memory_space=pl.ANY)
        kernel = _slot_kernel_whole
        kw = {}
    else:
        lb = min(L, _L_BLOCK)
        tail = (0,) * (cache.ndim - 2)
        blk = (1, lb) + cache.shape[2:]
        rblk = (1, 1) + rows.shape[2:]
        grid = (B, pl.cdiv(L, lb))
        in_specs = [pl.BlockSpec(blk, lambda b, j, *_: (b, j) + tail),
                    pl.BlockSpec(rblk, lambda b, j, *_: (b, 0) + tail)]
        out_specs = pl.BlockSpec(blk, lambda b, j, *_: (b, j) + tail)
        kernel = _slot_kernel_block
        kw = {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={1: 0},
        interpret=interpret,
        **kw,
    )(pos, cache, rows)


# ----------------------------------------------------------- paged form

def _paged_kernel_whole(phys_ref, off_ref, valid_ref, pages_ref,
                        rows_ref, out_ref):
    """Grid-free body (interpret / CPU): the writer-index reduction of
    nn/functional/flash_attention._paged_cache_write computed entirely
    in-kernel — one pass over the pool, mask and gather never touch
    HBM."""
    NP, PS = pages_ref.shape[0], pages_ref.shape[1]
    n = rows_ref.shape[0]
    phys = phys_ref[:]                                   # [n]
    off = off_ref[:]
    valid = valid_ref[:] != 0
    hp = ((phys[:, None] == lax.broadcasted_iota(jnp.int32, (n, NP), 1))
          & valid[:, None]).astype(jnp.int32)            # [n, NP]
    ho = (off[:, None] == lax.broadcasted_iota(
        jnp.int32, (n, PS), 1)).astype(jnp.int32)        # [n, PS]
    writer = jnp.einsum("np,no,n->po", hp, ho,
                        jnp.arange(n, dtype=jnp.int32))  # [NP, PS]
    mask = jnp.einsum("np,no->po", hp, ho) > 0
    vals = jnp.take(rows_ref[...], writer, axis=0)       # [NP, PS, ...]
    extra = (None,) * (pages_ref.ndim - 2)
    out_ref[...] = jnp.where(mask[(...,) + extra],
                             vals.astype(out_ref.dtype),
                             pages_ref[...])


def _paged_kernel_page(phys_ref, off_ref, valid_ref, pages_ref,
                       rows_ref, out_ref):
    """Gridded body (TPU): one program per physical page. Scans the
    B*S write candidates with a fori; every candidate owning this page
    blends its row at its offset. Exclusivity (at most one writer per
    (page, offset)) is the caller's copy-on-write invariant."""
    p = pl.program_id(0)
    o_ids = lax.broadcasted_iota(jnp.int32, pages_ref.shape, 1)
    int8 = pages_ref.dtype == jnp.int8

    def body(i, acc):
        row = rows_ref[pl.ds(i, 1)][None]                # [1, 1, ...]
        hit = (phys_ref[i] == p) & (valid_ref[i] != 0)
        # a miss compares against offset -1, which no iota lane holds
        # (scalar select in SMEM; the vector mask stays full-rank)
        sel = o_ids == jnp.where(hit, off_ref[i], -1)
        return jnp.where(sel, row.astype(acc.dtype), acc)

    # rolled loop: unroll=True would replicate the body n times in
    # EVERY one of the NP grid programs (n * NP code blow-up, Mosaic
    # compile time + VMEM) even though each page matches at most a few
    # of the candidates. int8 pages blend in int32 (no packed-int8
    # select on the VPU).
    page = pages_ref[...]
    acc = lax.fori_loop(0, rows_ref.shape[0], body,
                        page.astype(jnp.int32) if int8 else page)
    out_ref[...] = acc.astype(out_ref.dtype)


def fused_paged_write(pages, rows_flat, phys, off, valid, *,
                      interpret: bool = False,
                      gridded: bool | None = None):
    """One-kernel paged-pool write.

    pages: [NP, PS, ...] pool half; rows_flat: [n, ...] incoming
    payloads (n = B*S, pre-quantized for int8 pools); phys/off/valid:
    [n] int32 physical page, in-page offset, and write-validity (live,
    wlen and table-bounds gating folded in by the caller). The pool is
    aliased to the output. ``gridded`` as in fused_slot_write.
    """
    NP, PS = pages.shape[0], pages.shape[1]
    phys = jnp.asarray(phys, jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    if gridded is None:
        gridded = not interpret
    if not gridded:
        grid = ()
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        out_specs = pl.BlockSpec(memory_space=pl.ANY)
        kernel = _paged_kernel_whole
        kw = {}
    else:
        pblk = (1, PS) + pages.shape[2:]
        grid = (NP,)
        in_specs = [pl.BlockSpec(pblk, lambda p, *_: (p, 0) + (0,)
                                 * (len(pblk) - 2)),
                    pl.BlockSpec(rows_flat.shape,
                                 lambda p, *_: (0,) * rows_flat.ndim)]
        out_specs = pl.BlockSpec(pblk, lambda p, *_: (p, 0) + (0,)
                                 * (len(pblk) - 2))
        kernel = _paged_kernel_page
        kw = {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",))}
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid,
            in_specs=in_specs, out_specs=out_specs),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        **kw,
    )(phys, off, valid, pages, rows_flat)
