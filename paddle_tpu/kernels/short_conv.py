"""The causal depthwise short convolution of a linear-attention mixer, then
SiLU and, where asked, the per-head L2 norm and a scale: one Pallas kernel
forward and one backward under a ``custom_vjp``.

Per head, with ``x`` the projection's output [S, d] and ``w`` the head's
taps [K, d] (tap ``j`` reads position ``t - K + 1 + j``, zero before the
sequence's start):

    pre_t = sum_j w_j * x_{t-K+1+j},   u = silu(pre),
    y = u                                   (v)
    y = u * rsqrt(sum(u^2) + eps) * scale   (q and k: the norm over d)

in float32, rounded once to the output's dtype. The backward kernel reads
x and the output's gradient, computes ``pre``, ``u`` and the norm again in
VMEM and writes dx in x's dtype and the taps' gradient in float32, summed
over the sequence in VMEM: no float32 [B, H, S, d] array goes to HBM in
either pass.

Grid: (batch, head, tiles of the sequence), the tile axis sequential. The
forward kernel walks the tiles left to right and keeps the last ``HALO``
rows of x (as float32) in the scratch rows before the tile. The backward
kernel walks them right to left: it reads the ``HALO`` rows of x before
its tile as a block of their own, and keeps the first ``HALO`` rows of the
pre-activation's gradient for the tile to their left, whose dx and tap
gradients reach ``K - 1`` positions into it. A shifted view of a tile is
a load from the scratch at a row offset of 0 - 3 (``_taps``).

Layout: x [B, H, S, d] (d taken as it comes: 96 and 192 are no multiples
of the 128 lanes, and the array's tiled layout in HBM already holds them
as 128 and 256, as it does for any [..., d] array), S a multiple of the
tile, the tile a multiple of ``HALO``; w [H, K, d] float32.
``short_conv_xla`` is the same chain in ``jax.numpy`` under autodiff: the
off-chip path and what the kernels are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["short_conv_pallas", "short_conv_xla", "HALO"]

# rows kept beside a tile: a whole bf16 sublane tile, at least K - 1
HALO = 16

_F32 = jnp.float32
_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _silu_norm(pre, normalize, eps, scale):
    """The output before rounding: SiLU of the pre-activation, then the
    L2 norm over the last axis and the scale where asked."""
    u = pre * jax.nn.sigmoid(pre)
    if not normalize:
        return u
    r = jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + eps)
    return u * (r * scale)


def _taps(buf_ref, w, at, tile, flip):
    """sum_j w_j * buf[at - s_j : at - s_j + tile], s_j = K - 1 - j (or
    buf[s_j:] with ``flip``: the transpose), in tap order."""
    out = None
    for j in range(w.shape[0]):
        s = w.shape[0] - 1 - j
        term = w[j:j + 1] * buf_ref[pl.ds(s if flip else at - s, tile), :]
        out = term if out is None else out + term
    return out


def _fwd_kernel(w_ref, x_ref, y_ref, xs_ref, *, normalize, eps, scale):
    i = pl.program_id(2)
    tile = x_ref.shape[2]

    @pl.when(i == 0)
    def _():
        xs_ref[pl.ds(0, HALO), :] = jnp.zeros((HALO, xs_ref.shape[1]), _F32)

    @pl.when(i > 0)
    def _():        # the last rows of the tile before
        xs_ref[pl.ds(0, HALO), :] = xs_ref[pl.ds(tile, HALO), :]

    xs_ref[pl.ds(HALO, tile), :] = x_ref[0, 0].astype(_F32)
    pre = _taps(xs_ref, w_ref[0], HALO, tile, False)
    y_ref[0, 0] = _silu_norm(pre, normalize, eps, scale).astype(y_ref.dtype)


def _bwd_kernel(w_ref, x_ref, before_ref, dy_ref, dx_ref, dw_ref, xs_ref,
                da_ref, acc_ref, *, normalize, eps, scale):
    i = pl.program_id(2)
    first = i == pl.num_programs(2) - 1     # the sequence's first tile
    tile = x_ref.shape[2]

    @pl.when(i == 0)
    def _():        # no tile after the sequence's last
        da_ref[pl.ds(tile, HALO), :] = jnp.zeros((HALO, da_ref.shape[1]),
                                                 _F32)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs_ref[pl.ds(0, HALO), :] = jnp.where(
        first, 0.0, before_ref[0, 0].astype(_F32))
    x = x_ref[0, 0].astype(_F32)
    xs_ref[pl.ds(HALO, tile), :] = x
    w = w_ref[0]
    pre = _taps(xs_ref, w, HALO, tile, False)
    sig = jax.nn.sigmoid(pre)
    u = pre * sig
    dy = dy_ref[0, 0].astype(_F32)
    if normalize:   # y = scale * u * r, r = (sum u^2 + eps)^-1/2
        r = jax.lax.rsqrt(jnp.sum(u * u, axis=-1, keepdims=True) + eps)
        uy = jnp.sum(u * dy, axis=-1, keepdims=True)
        dy = (scale * r) * (dy - u * (r * r * uy))
    # rows [tile:] still hold the first rows of the tile after
    da_ref[pl.ds(0, tile), :] = dy * (sig * (1.0 + pre * (1.0 - sig)))
    dx_ref[0, 0] = _taps(da_ref, w, 0, tile, True).astype(dx_ref.dtype)
    for j in range(w.shape[0]):
        s = w.shape[0] - 1 - j
        acc_ref[j:j + 1, :] += jnp.sum(x * da_ref[pl.ds(s, tile), :], axis=0,
                                       keepdims=True)
    da_ref[pl.ds(tile, HALO), :] = da_ref[pl.ds(0, HALO), :]

    @pl.when(first)
    def _():
        dw_ref[0, 0] = acc_ref[...]


def _tile_spec(shape, tile, reverse):
    nt = shape[2] // tile
    at = (lambda b, h, i: (b, h, nt - 1 - i, 0)) if reverse \
        else (lambda b, h, i: (b, h, i, 0))
    return pl.BlockSpec((1, 1, tile, shape[3]), at)


def _w_spec(w):
    return pl.BlockSpec((1,) + w.shape[1:], lambda b, h, i: (h, 0, 0))


def _params(normalize, eps, scale):
    return dict(normalize=normalize, eps=eps, scale=scale)


def _pallas_fwd(x, w, tile, normalize, eps, scale, interpret):
    bsz, heads, seq, d = x.shape
    blk = _tile_spec(x.shape, tile, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **_params(normalize, eps, scale)),
        grid=(bsz, heads, seq // tile), in_specs=[_w_spec(w), blk],
        out_specs=blk, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((tile + HALO, d), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name="short_conv_fwd")(w, x)


def _pallas_bwd(x, w, dy, tile, normalize, eps, scale, interpret):
    bsz, heads, seq, d = x.shape
    taps, nt, per = w.shape[1], seq // tile, tile // HALO
    blk = _tile_spec(x.shape, tile, True)
    # the HALO rows before the tile (a block of its own; none before the
    # first, whose rows the kernel zeroes)
    before = pl.BlockSpec(
        (1, 1, HALO, d),
        lambda b, h, i: (b, h, jnp.maximum((nt - 1 - i) * per - 1, 0), 0))
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, **_params(normalize, eps, scale)),
        grid=(bsz, heads, nt), in_specs=[_w_spec(w), blk, before, blk],
        out_specs=[blk, pl.BlockSpec((1, 1, taps, d),
                                     lambda b, h, i: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, heads, taps, d), _F32)],
        scratch_shapes=[pltpu.VMEM((tile + HALO, d), _F32),
                        pltpu.VMEM((tile + HALO, d), _F32),
                        pltpu.VMEM((taps, d), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name="short_conv_bwd")(w, x, x, dy)
    return dx, jnp.sum(dw, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _chain(x, w, tile, normalize, eps, scale, interpret):
    return _pallas_fwd(x, w, tile, normalize, eps, scale, interpret)


def _chain_fwd(x, w, tile, normalize, eps, scale, interpret):
    return _pallas_fwd(x, w, tile, normalize, eps, scale, interpret), (x, w)


def _chain_bwd(tile, normalize, eps, scale, interpret, res, dy):
    x, w = res
    return _pallas_bwd(x, w, dy, tile, normalize, eps, scale, interpret)


_chain.defvjp(_chain_fwd, _chain_bwd)


def short_conv_pallas(x, w, tile: int, normalize: bool = False,
                      eps: float = 1e-6, scale: float = 1.0,
                      interpret: bool = False):
    """The chain as two Pallas TPU kernels (``interpret``: the same grid
    through the Pallas interpreter); x [B, H, S, d], w [H, K, d] float32."""
    seq, taps = x.shape[2], w.shape[1]
    if tile % HALO or seq % tile or taps - 1 > HALO:
        raise ValueError(f"the tile {tile} must be a multiple of {HALO} that "
                         f"divides the sequence {seq}, and at most {HALO} + 1"
                         f" taps ({taps})")
    return _chain(x, w.astype(_F32), tile, normalize, eps, scale, interpret)


def short_conv_xla(x, w, normalize: bool = False, eps: float = 1e-6,
                   scale: float = 1.0):
    """The same chain in ``jax.numpy`` (differentiated by JAX): any
    backend."""
    seq, taps = x.shape[2], w.shape[1]
    xf = jnp.pad(x.astype(_F32), [(0, 0), (0, 0), (taps - 1, 0), (0, 0)])
    w = w.astype(_F32)
    pre = sum(xf[:, :, j:j + seq] * w[None, :, j:j + 1]
              for j in range(taps))
    return _silu_norm(pre, normalize, eps, scale).astype(x.dtype)
