"""EQuARX-style quantized all-reduce (PAPERS.md: arXiv 2506.17615).

SURVEY.md §5.8 lists block-quantized allreduce as the TPU-native option on
top of the HLO collectives. Scheme (the paper's two-phase design):

  1. reduce-scatter phase as an all-to-all of int8 payloads: each shard
     block-quantizes the chunk destined for every peer (per-block max-abs
     scale) and exchanges q(int8) + scales(f32/block) — ~4x fewer wire
     bytes than f32, ~2x fewer than bf16;
  2. each shard dequantizes the N received chunks and accumulates in
     f32 (no int8 overflow), producing its exactly-reduced chunk;
  3. all-gather phase: the reduced chunk is re-quantized and gathered,
     every shard dequantizes the full result.

Quantization error: one rounding per hop (2 total), bounded by
block_max/254 per element per hop. Exposed eagerly here and usable for
DP gradient reduction where bandwidth, not precision, binds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .collective import (Group, _default_group, _raw, _to_local,
                         _to_stacked)

__all__ = ["quantized_all_reduce", "quantized_reduce_scatter",
           "quantized_all_gather"]


def _quantize(x, block: int, qmax: float):
    """x [M] (M % block == 0) -> (q int8 [M], scale f32 [M/block])."""
    xb = x.reshape(-1, block)
    scale = jnp.max(jnp.abs(xb), axis=1) / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[:, None]), -qmax, qmax)
    return q.astype(jnp.int8).reshape(-1), scale


def _dequantize(q, scale, block: int):
    return (q.astype(jnp.float32).reshape(-1, block)
            * scale[:, None]).reshape(-1)


@functools.lru_cache(maxsize=64)
def _qar_program(axis: str, mesh, n: int, padded: int, block: int):
    qmax = 127.0
    chunk = padded // n

    def body(x):
        # x: local [1, padded] f32
        flat = x[0]
        # chunks[j] goes to peer j — quantize each independently
        chunks = flat.reshape(n, chunk)
        q, s = _quantize(chunks.reshape(-1), block, qmax)
        q = q.reshape(n, chunk)
        s = s.reshape(n, chunk // block)
        # phase 1: all-to-all of int8 + scales (the RS wire transfer)
        q_recv = lax.all_to_all(q[None], axis, split_axis=1,
                                concat_axis=0, tiled=False)[:, 0]
        s_recv = lax.all_to_all(s[None], axis, split_axis=1,
                                concat_axis=0, tiled=False)[:, 0]
        # local f32 accumulate of my chunk over all peers
        deq = jax.vmap(lambda qq, ss: _dequantize(qq, ss, block))(
            q_recv, s_recv)
        mine = jnp.sum(deq, axis=0)                      # [chunk] f32
        # phase 2: re-quantize + all-gather (the AG wire transfer)
        q2, s2 = _quantize(mine, block, qmax)
        q_all = lax.all_gather(q2, axis, axis=0, tiled=True)
        s_all = lax.all_gather(s2, axis, axis=0, tiled=True)
        out = _dequantize(q_all, s_all, block)           # [padded]
        return out[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                       out_specs=P(axis))
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# In-program (shard_map-body) collectives for the ZeRO train step.
#
# These run INSIDE an enclosing jax.shard_map over the data axes of the
# training mesh (distributed/parallel_step.py): the argument is this
# device's LOCAL array, `axis` names the mesh axis to communicate over,
# and the wire payload is int8 q + f32 per-block scales ("int8") or a
# bf16 cast ("bf16") — accumulation is always f32 (no low-precision
# overflow). Padded-tail exact: tails padded to the block size quantize
# as zero blocks (scale 0 -> safe divisor 1), so padding never perturbs
# real elements and is sliced off before returning.
# ---------------------------------------------------------------------------

def _pad_flat(flat, multiple: int):
    """flat [L] -> [ceil(L/multiple)*multiple], zero-padded tail."""
    size = flat.shape[0]
    padded = -(-size // max(1, multiple)) * max(1, multiple)
    if padded == size:
        return flat
    return jnp.pad(flat, (0, padded - size))


def _wire_multiple(precision: str, block: int) -> int:
    """Alignment the wire payload needs: int8 pads to the scale block;
    bf16 has no per-block scales, so no padding beyond the element."""
    return block if precision == "int8" else 1


def _wire_encode(flat, precision: str, block: int):
    """flat f32 [P] (block-aligned) -> wire payload tuple.

    bf16 payloads travel bitcast to uint16: backends without native
    bf16 (XLA:CPU float normalization) silently upcast bf16 collectives
    back to f32, which would erase the wire saving — an integer payload
    is moved verbatim everywhere, and the bitcast is free on TPU."""
    if precision == "int8":
        q, s = _quantize(flat, block, 127.0)
        return (q, s)
    if precision == "bf16":
        return (lax.bitcast_convert_type(flat.astype(jnp.bfloat16),
                                         jnp.uint16),)
    raise ValueError(f"unknown comm precision {precision!r}")


def _wire_decode(payload, precision: str, block: int):
    """wire payload -> f32 flat array."""
    if precision == "int8":
        q, s = payload
        return _dequantize(q, s, block)
    return lax.bitcast_convert_type(
        payload[0], jnp.bfloat16).astype(jnp.float32)


def body_reduce_scatter(x, axis: str, n: int, dim: int,
                        precision: str, block: int = 256):
    """Sum-reduce-scatter of a local partial `x` over mesh axis `axis`
    inside a shard_map body: every device contributes its full-shape
    partial and receives the f32-exact sum of its 1/n chunk along `dim`
    (which must divide evenly). Wire transfer is one all-to-all of the
    quantized/cast chunks; accumulation is f32."""
    orig_dtype = x.dtype
    parts = jnp.split(x.astype(jnp.float32), n, axis=dim)
    part_shape = parts[0].shape
    mult = _wire_multiple(precision, block)
    flat = jnp.stack([_pad_flat(p.reshape(-1), mult) for p in parts])
    payload = _wire_encode(flat.reshape(-1), precision, block)
    payload = tuple(p.reshape((n, -1)) for p in payload)
    recv = tuple(lax.all_to_all(p, axis, split_axis=0, concat_axis=0,
                                tiled=True) for p in payload)
    deq = jax.vmap(lambda *row: _wire_decode(row, precision, block))(*recv)
    mine = jnp.sum(deq, axis=0)                       # [padded] f32
    size = 1
    for d in part_shape:
        size *= int(d)
    return mine[:size].reshape(part_shape).astype(orig_dtype)


def body_all_gather(shard, axis: str, n: int, dim: int,
                    precision: str, block: int = 256):
    """All-gather of a local `shard` over mesh axis `axis` inside a
    shard_map body, concatenating the n shards along `dim`. The wire
    transfer moves the quantized/cast shard; every device dequantizes
    the gathered payload back to the shard dtype."""
    orig_dtype = shard.dtype
    flat = _pad_flat(shard.astype(jnp.float32).reshape(-1),
                     _wire_multiple(precision, block))
    payload = _wire_encode(flat, precision, block)
    recv = tuple(lax.all_gather(p, axis, axis=0, tiled=False)
                 for p in payload)
    deq = jax.vmap(lambda *row: _wire_decode(row, precision, block))(*recv)
    size = 1
    for d in shard.shape:
        size *= int(d)
    pieces = deq[:, :size].reshape((n,) + tuple(shard.shape))
    return jnp.concatenate([pieces[i] for i in range(n)],
                           axis=dim).astype(orig_dtype)


def body_all_reduce(x, axis: str, n: int, precision: str,
                    block: int = 256):
    """Two-phase sum-all-reduce inside a shard_map body (the EQuARX
    construction): all-to-all of encoded chunks -> f32 accumulate ->
    re-encode -> all-gather. Both hops move low-precision bytes."""
    orig_dtype = x.dtype
    shape = tuple(x.shape)
    size = 1
    for d in shape:
        size *= int(d)
    mult = _wire_multiple(precision, block)
    chunk = -(-size // n)
    chunk = -(-chunk // mult) * mult
    flat = jnp.pad(x.astype(jnp.float32).reshape(-1),
                   (0, chunk * n - size))
    payload = _wire_encode(flat, precision, block)
    payload = tuple(p.reshape((n, -1)) for p in payload)
    recv = tuple(lax.all_to_all(p, axis, split_axis=0, concat_axis=0,
                                tiled=True) for p in payload)
    deq = jax.vmap(lambda *row: _wire_decode(row, precision, block))(*recv)
    mine = jnp.sum(deq, axis=0)                       # [chunk] f32
    payload2 = _wire_encode(mine, precision, block)
    recv2 = tuple(lax.all_gather(p, axis, axis=0, tiled=False)
                  for p in payload2)
    full = jax.vmap(lambda *row: _wire_decode(row, precision, block))(
        *recv2).reshape(-1)
    return full[:size].reshape(shape).astype(orig_dtype)


@functools.lru_cache(maxsize=64)
def _rs_program(axis: str, mesh, n: int, dim: int, block: int):
    def body(x):
        return body_reduce_scatter(x[0], axis, n, dim, "int8",
                                   block)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _ag_program(axis: str, mesh, n: int, dim: int, block: int):
    def body(x):
        return body_all_gather(x[0], axis, n, dim, "int8", block)[None]

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(fn)


def quantized_reduce_scatter(tensor, group: Group = None,
                             block: int = 256, dim: int = 0):
    """Sum-reduce-scatter through 8-bit block-quantized wire transfers.

    Stacked single-controller convention (collective.all_reduce): input
    [N, *S] where row k is rank k's partial; `S[dim]` must divide by N.
    Returns [N, *chunk] where row k is rank k's f32-summed 1/N chunk of
    the total along `dim`. One quantized all-to-all on the wire; one
    rounding per element (bounded by N * block_max / 254)."""
    group = group or _default_group()
    x = _raw(tensor)
    n = group.nranks
    stacked = _to_stacked(group, x)
    shape = tuple(stacked.shape[1:])
    if shape[dim] % n != 0:
        raise ValueError(
            f"reduce_scatter dim {dim} (size {shape[dim]}) must divide "
            f"by the group size {n}")
    mesh = group.mesh
    flat = jax.device_put(stacked.astype(jnp.float32),
                          NamedSharding(mesh, P(group.axis)))
    prog = _rs_program(group.axis, mesh, n, dim, block)
    out = prog(flat).astype(stacked.dtype)
    out = _to_local(out, group)
    if isinstance(tensor, Tensor):
        tensor.value = out
        return tensor
    return Tensor(out)


def quantized_all_gather(tensor, group: Group = None, block: int = 256,
                         dim: int = 0):
    """All-gather through 8-bit block-quantized wire transfers.

    Stacked convention: input [N, *S] where row k is rank k's shard;
    output [N, *full] (full = S with dim scaled by N), every row the
    identical concatenation. One rounding per element (block_max/254)."""
    group = group or _default_group()
    x = _raw(tensor)
    n = group.nranks
    stacked = _to_stacked(group, x)
    mesh = group.mesh
    flat = jax.device_put(stacked.astype(jnp.float32),
                          NamedSharding(mesh, P(group.axis)))
    prog = _ag_program(group.axis, mesh, n, dim, block)
    out = prog(flat).astype(stacked.dtype)
    out = _to_local(out, group)
    if isinstance(tensor, Tensor):
        tensor.value = out
        return tensor
    return Tensor(out)


def quantized_all_reduce(tensor, group: Group = None, block: int = 256):
    """Sum-all-reduce through 8-bit block-quantized wire transfers.

    Same calling convention as collective.all_reduce (stacked [N, *S]
    single-controller; this rank's [*S] under a multi-process world).
    Trades exactness (two bounded roundings) for ~4x wire bytes vs f32.
    """
    group = group or _default_group()
    x = _raw(tensor)
    n = group.nranks
    stacked = _to_stacked(group, x)
    shape = stacked.shape[1:]
    size = 1
    for d in shape:
        size *= int(d)
    # pad so every rank-chunk is block-aligned
    chunk = -(-size // n)
    chunk = -(-chunk // block) * block
    padded = chunk * n
    flat = jnp.pad(stacked.reshape(n, size).astype(jnp.float32),
                   ((0, 0), (0, padded - size)))
    mesh = group.mesh
    flat = jax.device_put(flat, NamedSharding(mesh, P(group.axis)))
    prog = _qar_program(group.axis, mesh, n, padded, block)
    out = prog(flat)[:, :size].reshape((n,) + shape).astype(stacked.dtype)
    out = _to_local(out, group)
    if isinstance(tensor, Tensor):
        tensor.value = out
        return tensor
    return Tensor(out)
