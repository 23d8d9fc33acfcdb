"""The chunked gated delta rule, forward and backward (Gated DeltaNet).

Per head, with state ``S`` [dv, dk] carried in float32 from ``S_0 = 0``:

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T,   o_t = S_t q_t

with ``a_t = exp(g_t)`` (Yang, Kautz and Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464). A chunk of ``C`` positions is computed at
once in the WY / UT form of Yang et al., arXiv:2406.06484. Within a chunk,
with ``G`` the cumulative sum of ``g``, ``E[t, s] = exp(G_t - G_s)`` (s <= t)
and ``H = S^T`` [dk, dv] the state the chunk starts from:

    A = diag(b) (K K^T * E), strictly lower;  T = (I + A)^-1
    W = T diag(b exp(G)) K;  U = T diag(b) V - W H
    O = diag(exp(G)) Q H + (Q K^T * E, lower) U
    H' = exp(G_C) H + (diag(exp(G_C - G)) K)^T U

Every exponent is of a difference that is never positive, so no decay
overflows however strong. ``T`` comes from the block formula
``[[X, 0], [Y, Z]]^-1 = [[X^-1, 0], [-Z^-1 Y X^-1, Z^-1]]`` applied at
block sizes 1, 2, 4, ... in C x C products (``_unit_lower_inverse``; from
block 8 on only the rows a merge changes): no power of ``A`` is formed, so
nothing cancels. Products run in float32 at full precision; where one
operand is q, k, v or the output's gradient as it came in bfloat16, the
float32 one is split exactly into three bfloat16 parts (``_mm``): three
MXU passes for full precision's six, the other three being zero. The
backward pass is the same chunk run backwards by hand (``_chunk_bwd``)
from the float32 state each chunk started from, which the forward pass
writes out, the chunks taken last to first with the state's gradient
carried between them.

One body per pass serves both backends: ``gated_delta_pallas`` runs it as a
Pallas TPU kernel, a grid of (batch, head, chunk) with the state in VMEM
across the chunk axis (``interpret=True`` runs the same grid off the chip);
``gated_delta_xla`` runs it under ``lax.scan`` over the chunks. Both are
one ``custom_vjp`` whose residuals are the inputs and the chunk states.
Its forward rule names the output and the chunk states
``DELTA_RULE_RESIDUAL`` (``jax.ad_checkpoint.checkpoint_name``) on every
backend: a ``jax.checkpoint`` policy that keeps the name
(``distributed.recompute``'s "full" and "dots") hands both to the backward
pass, and a recomputed block never runs the forward pass a second time.
Outside a checkpoint the name lowers to nothing.

Layout: q, k [B, H, S, dk]; v [B, H, S, dv]; g (log of the decay, <= 0)
and b [B, H, S] float32; S a multiple of the chunk. The caller puts the
scale on q. Head sizes are taken as they come (96 and 192 are
no multiples of the 128 lanes: Mosaic pads them in VMEM, nothing is padded
in HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_pallas", "gated_delta_xla", "DELTA_RULE_RESIDUAL"]

# the checkpoint_name of the forward rule's output [B, H, S, dv] and float32
# chunk states [B, H, S // chunk, dk, dv]; why the named policies keep it:
# distributed/recompute.py
DELTA_RULE_RESIDUAL = "delta_rule_residual"

_HIGHEST = lax.Precision.HIGHEST
_SPLIT = {jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)}

_tally = [0]            # products split so far, as traced (``_mm``)
_split_traced: dict = {}   # (pass, q, k, v dtypes) -> products split


def _parts(x):
    """float32 -> (lo, mid, hi) bfloat16 with lo + mid + hi == x exactly."""
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return (r - mid.astype(jnp.float32)).astype(jnp.bfloat16), mid, hi


def _mm(a, b, ta=False, tb=False):
    """a @ b in float32 (a^T with ``ta``, b^T with ``tb``); float32
    operands at full precision. A float32 operand against a bfloat16 one
    (q, k, v or the output's gradient as they came in) is split exactly
    into three bfloat16 parts: three one-pass products, which are the terms
    of full precision's six that are not zero."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    dot = functools.partial(lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)
    if {a.dtype, b.dtype} == _SPLIT:
        _tally[0] += 1
        if a.dtype == jnp.float32:
            lo, mid, hi = (dot(x, b) for x in _parts(a))
        else:
            lo, mid, hi = (dot(a, x) for x in _parts(b))
        return (lo + mid) + hi
    prec = _HIGHEST if jnp.float32 in (a.dtype, b.dtype) else None
    return dot(a, b, precision=prec)


def _tallied(pass_):
    """Record, as a chunk body is traced, how many of its products ``_mm``
    split, by pass and by the dtypes of q, k and v."""
    def wrap(body):
        @functools.wraps(body)
        def traced(*args):
            before = _tally[0]
            out = body(*args)
            key = (pass_,) + tuple(jnp.dtype(x.dtype).name for x in args[:3])
            _split_traced[key] = _tally[0] - before
            return out
        return traced
    return wrap


def split_products(q_dtype, k_dtype, v_dtype) -> dict:
    """Products of each chunk body that took the three-pass form, as last
    traced with q, k and v of these dtypes (0 where not traced)."""
    dts = tuple(jnp.dtype(d).name for d in (q_dtype, k_dtype, v_dtype))
    return {p: _split_traced.get((p,) + dts, 0)
            for p in ("forward", "backward")}


def _iota(c):
    t = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    s = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return t, s


def _to_col(row):
    """(1, C) -> (C, 1), by a masked sum (no transpose of a vector)."""
    t, s = _iota(row.shape[-1])
    return jnp.sum(jnp.where(t == s, row, 0.0), axis=1, keepdims=True)


def _to_row(col):
    t, s = _iota(col.shape[0])
    return jnp.sum(jnp.where(t == s, col, 0.0), axis=0, keepdims=True)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower [C, C], C a power of two: the
    inverse at block size 2m from the one at m, ``B - B Y B``, Y the part
    of ``a`` that joins two blocks of m inside one of 2m. Y has rows in the
    lower half of each 2m block alone and B is block-diagonal at m, so
    ``B Y B`` is zero elsewhere: from m = 8 on (a slice along whole
    sublane tiles) only those rows are computed."""
    c = a.shape[0]
    t, s = _iota(c)
    x = t ^ s               # t and s share a block of m iff x < m
    inv = jnp.where(t == s, 1.0, 0.0) - jnp.where(x < 2, a, 0.0)
    m = 2
    while m < c:
        y = jnp.where((x >= m) & (x < 2 * m), a, 0.0)
        if m < 8:
            inv = inv - _mm(_mm(inv, y), inv)
        else:
            halves = inv.reshape(c // (2 * m), 2, m, c)
            low = halves[:, 1].reshape(c // 2, c)
            low = low - _mm(_mm(low, y), inv)
            inv = jnp.concatenate(
                [halves[:, :1], low.reshape(c // (2 * m), 1, m, c)],
                axis=1).reshape(c, c)
        m *= 2
    return inv


def _prepare(k, g_row, b_row):
    """What both passes of a chunk recompute: the decays and ``T``."""
    t, s = _iota(k.shape[0])
    low, strict = s <= t, s < t
    gam = jnp.sum(jnp.where(low, g_row, 0.0), axis=1, keepdims=True)
    gam_row = _to_row(gam)
    e = jnp.exp(jnp.where(low, gam - gam_row, -jnp.inf))
    gc = jnp.sum(g_row, axis=1, keepdims=True)                  # (1, 1)
    b = _to_col(b_row)
    kk = _mm(k, k, tb=True)
    a = jnp.where(strict, b * kk * e, 0.0)
    return dict(low=low, strict=strict, gam=gam, e=e, gc=gc, b=b, kk=kk,
                tinv=_unit_lower_inverse(a), decay=jnp.exp(gam),
                bd_row=b_row * jnp.exp(gam_row), to_end=jnp.exp(gc - gam))


# In both bodies q, k, v and do enter the products as they came in, each
# alone on its side; a row or column scaling of theirs (b, decay, to_end)
# goes onto the other operand or onto the result (``_mm`` splits the
# float32 side when they came in bfloat16).

@_tallied("forward")
def _chunk_fwd(q, k, v, g_row, b_row, h):
    """One chunk: (o [C, dv] float32, the state after it [dk, dv])."""
    p = _prepare(k, g_row, b_row)
    tinv = p["tinv"]
    w = _mm(tinv * p["bd_row"], k)                # T diag(b decay) K
    u = _mm(tinv * b_row, v) - _mm(w, h)          # T diag(b) V - W H
    qk = jnp.where(p["low"], _mm(q, k, tb=True) * p["e"], 0.0)
    o = p["decay"] * _mm(q, h) + _mm(qk, u)
    h_next = jnp.exp(p["gc"]) * h + _mm(k, p["to_end"] * u, ta=True)
    return o, h_next


@_tallied("backward")
def _chunk_bwd(q, k, v, g_row, b_row, h, do, dh):
    """One chunk's gradients from the state it started from ``h``, the
    output's gradient ``do`` and the gradient of the state after it
    ``dh``: (dq, dk, dv [C, d] float32, dg, db (1, C), the gradient of
    ``h``)."""
    p = _prepare(k, g_row, b_row)
    low, strict, e, b = p["low"], p["strict"], p["e"], p["b"]
    decay, to_end, tinv, bd_row = p["decay"], p["to_end"], p["tinv"], \
        p["bd_row"]
    qf, kf, vf, dof = (x.astype(jnp.float32) for x in (q, k, v, do))
    w = _mm(tinv * bd_row, k)
    u = _mm(tinv * b_row, v) - _mm(w, h)
    qk = _mm(q, k, tb=True)
    pm = jnp.where(low, qk * e, 0.0)
    egc = jnp.exp(p["gc"])

    # O = decay * (Q H) + P U;  H' = exp(gc) H + (to_end * K)^T U
    du = _mm(pm, do, ta=True) + to_end * _mm(k, dh)
    dp = jnp.where(low, _mm(do, u, tb=True), 0.0)
    dq = decay * _mm(do, h, tb=True)
    d_decay = jnp.sum(_mm(q, h) * dof, axis=1, keepdims=True)
    dkd = _mm(u, dh, tb=True)
    dk = to_end * dkd
    r = to_end * jnp.sum(kf * dkd, axis=1, keepdims=True)     # d(gc - gam)
    dgc = jnp.sum(r, axis=0, keepdims=True) \
        + egc * jnp.sum(jnp.sum(h * dh, axis=1, keepdims=True), axis=0,
                        keepdims=True)
    dgam = -r
    dh_prev = egc * dh + _mm(decay * qf, do, ta=True) - _mm(w, du, ta=True)
    # U = T Vb - W H;  W = T Kg
    dw = -_mm(du, h, tb=True)
    dt = _mm(dw, k, tb=True) * bd_row + _mm(du, v, tb=True) * b_row
    dkg, dvb = _mm(tinv, dw, ta=True), _mm(tinv, du, ta=True)
    # T = (I + A)^-1;  A = b * (K K^T) * E, strictly lower
    da = jnp.where(strict, -_mm(_mm(tinv, dt, ta=True), tinv, tb=True), 0.0)
    dkk = da * b * e
    db = jnp.sum(da * p["kk"] * e, axis=1, keepdims=True)
    de = da * b * p["kk"]
    # P = (Q K^T) * E, lower
    dqk = dp * e
    de = de + dp * qk
    dq = dq + _mm(dqk, k)
    dk = dk + _mm(dqk, q, ta=True) + _mm(dkk, k) + _mm(dkk, k, ta=True)
    # Kg = b * decay * K;  Vb = b * V
    dk = dk + b * decay * dkg
    t1 = jnp.sum(kf * dkg, axis=1, keepdims=True)
    db = db + decay * t1 + jnp.sum(vf * dvb, axis=1, keepdims=True)
    d_decay = d_decay + b * t1
    dv = b * dvb
    # E = exp(gam_t - gam_s);  decay = exp(gam);  gam = cumsum(g);  gc = sum(g)
    f = de * e
    dgam = dgam + jnp.sum(f, axis=1, keepdims=True) \
        - _to_col(jnp.sum(f, axis=0, keepdims=True)) + d_decay * decay
    dg = jnp.sum(jnp.where(low, dgam, 0.0), axis=0, keepdims=True) + dgc
    return dq, dk, dv, dg, _to_row(db), dh_prev


# ------------------------------------------------------------ Pallas TPU

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, h_ref):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    h = h_ref[...]
    st_ref[0, 0, 0] = h
    o, h_next = _chunk_fwd(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
                           g_ref[0, 0, pl.ds(c, 1), :],
                           b_ref[0, 0, pl.ds(c, 1), :], h)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    h_ref[...] = h_next


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dh_ref):
    i = pl.program_id(2)
    c = pl.num_programs(2) - 1 - i          # the chunks last to first

    @pl.when(i == 0)
    def _():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    dq, dk, dv, dg, db, dh_prev = _chunk_bwd(
        q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], g_ref[0, 0, pl.ds(c, 1), :],
        b_ref[0, 0, pl.ds(c, 1), :], st_ref[0, 0, 0], do_ref[0, 0],
        dh_ref[...])
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    dg_ref[0, 0, pl.ds(c, 1), :] = dg
    db_ref[0, 0, pl.ds(c, 1), :] = db
    dh_ref[...] = dh_prev


def _specs(shape_k, shape_v, nc, chunk, reverse):
    bsz, heads, _, dk = shape_k
    dv = shape_v[-1]
    at = (lambda b, h, i: (b, h, nc - 1 - i, 0)) if reverse \
        else (lambda b, h, i: (b, h, i, 0))
    qk = pl.BlockSpec((1, 1, chunk, dk), at)
    vv = pl.BlockSpec((1, 1, chunk, dv), at)
    rows = pl.BlockSpec((1, 1, nc, chunk), lambda b, h, i: (b, h, 0, 0))
    st = pl.BlockSpec((1, 1, 1, dk, dv),
                      lambda b, h, i: (b, h, at(b, h, i)[2], 0, 0))
    return qk, vv, rows, st


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _pallas_fwd(q, k, v, g, b, chunk, interpret):
    bsz, heads, seq, dk = q.shape
    dv = v.shape[-1]
    nc = seq // chunk
    qk, vv, rows, st = _specs(q.shape, v.shape, nc, chunk, False)
    return pl.pallas_call(
        _fwd_kernel, grid=(bsz, heads, nc),
        in_specs=[qk, qk, vv, rows, rows], out_specs=[vv, st],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz, heads, nc, dk, dv),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name="gated_delta_fwd",
    )(q, k, v, g.reshape(bsz, heads, nc, chunk),
      b.reshape(bsz, heads, nc, chunk))


def _pallas_bwd(q, k, v, g, b, states, do, chunk, interpret):
    bsz, heads, seq, dk = q.shape
    dv = v.shape[-1]
    nc = seq // chunk
    qk, vv, rows, st = _specs(q.shape, v.shape, nc, chunk, True)
    row_shape = jax.ShapeDtypeStruct((bsz, heads, nc, chunk), jnp.float32)
    dq, dk_, dv_, dg, db = pl.pallas_call(
        _bwd_kernel, grid=(bsz, heads, nc),
        in_specs=[qk, qk, vv, rows, rows, st, vv],
        out_specs=[qk, qk, vv, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   row_shape, row_shape],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name="gated_delta_bwd",
    )(q, k, v, g.reshape(bsz, heads, nc, chunk),
      b.reshape(bsz, heads, nc, chunk), states, do)
    return (dq, dk_, dv_, dg.reshape(g.shape).astype(g.dtype),
            db.reshape(b.shape).astype(b.dtype))


# ------------------------------------------------------------ XLA scan

def _chunks(x, chunk):
    """[B, H, S, ...] -> [S // chunk, B, H, chunk, ...]."""
    bsz, heads, seq = x.shape[:3]
    x = x.reshape((bsz, heads, seq // chunk, chunk) + x.shape[3:])
    return jnp.moveaxis(x, 2, 0)


def _unchunks(x):
    x = jnp.moveaxis(x, 0, 2)
    return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])


def _xla_fwd(q, k, v, g, b, chunk):
    bsz, heads, _, dk = q.shape
    one = jax.vmap(jax.vmap(_chunk_fwd))

    def body(h, xs):
        qc, kc, vc, gc, bc = xs
        o, h_next = one(qc, kc, vc, gc[:, :, None], bc[:, :, None], h)
        return h_next, (o.astype(v.dtype), h)

    h0 = jnp.zeros((bsz, heads, dk, v.shape[-1]), jnp.float32)
    _, (o, states) = lax.scan(body, h0, tuple(
        _chunks(x, chunk) for x in (q, k, v, g, b)))
    return _unchunks(o), jnp.moveaxis(states, 0, 2)


def _xla_bwd(q, k, v, g, b, states, do, chunk):
    one = jax.vmap(jax.vmap(_chunk_bwd))

    def body(dh, xs):
        qc, kc, vc, gc, bc, hc, doc = xs
        dq, dk, dv, dg, db, dh_prev = one(qc, kc, vc, gc[:, :, None],
                                          bc[:, :, None], hc, doc, dh)
        return dh_prev, (dq.astype(q.dtype), dk.astype(k.dtype),
                         dv.astype(v.dtype), dg[:, :, 0], db[:, :, 0])

    dh0 = jnp.zeros(states.shape[:2] + states.shape[3:], jnp.float32)
    xs = tuple(_chunks(x, chunk) for x in (q, k, v, g, b))
    xs += (jnp.moveaxis(states, 2, 0), _chunks(do, chunk))
    _, grads = lax.scan(body, dh0, xs, reverse=True)
    dq, dk, dv, dg, db = (_unchunks(x) for x in grads)
    return dq, dk, dv, dg.astype(g.dtype), db.astype(b.dtype)


# ------------------------------------------------------------ the rule

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, b, chunk, backend):
    return _rule_fwd(q, k, v, g, b, chunk, backend)[0]


def _rule_fwd(q, k, v, g, b, chunk, backend):
    if backend == "xla":
        o, states = _xla_fwd(q, k, v, g, b, chunk)
    else:
        o, states = _pallas_fwd(q, k, v, g, b, chunk,
                                interpret=backend == "interpret")
    o, states = (checkpoint_name(x, DELTA_RULE_RESIDUAL) for x in (o, states))
    return o, (q, k, v, g, b, states)


def _rule_bwd(chunk, backend, res, do):
    q, k, v, g, b, states = res
    if backend == "xla":
        return _xla_bwd(q, k, v, g, b, states, do, chunk)
    return _pallas_bwd(q, k, v, g, b, states, do, chunk,
                       interpret=backend == "interpret")


_rule.defvjp(_rule_fwd, _rule_bwd)


def _check(q, k, v, g, b, chunk):
    seq = q.shape[2]
    if chunk & (chunk - 1) or chunk < 8 or seq % chunk:
        raise ValueError(f"chunk {chunk} must be a power of two >= 8 that "
                         f"divides the sequence {seq}")
    if k.shape != q.shape or v.shape[:3] != q.shape[:3] \
            or g.shape != q.shape[:3] or b.shape != q.shape[:3]:
        raise ValueError("q, k [B, H, S, dk], v [B, H, S, dv], g and b "
                         f"[B, H, S]: got {q.shape}, {k.shape}, {v.shape}, "
                         f"{g.shape}, {b.shape}")


def gated_delta_pallas(q, k, v, g, b, chunk: int = 128,
                       interpret: bool = False):
    """The rule as two Pallas TPU kernels (``interpret``: the same grid
    through the Pallas interpreter)."""
    _check(q, k, v, g, b, chunk)
    return _rule(q, k, v, g, b, chunk, "interpret" if interpret else "pallas")


def gated_delta_xla(q, k, v, g, b, chunk: int = 128):
    """The rule's chunk bodies under ``lax.scan``: any backend."""
    _check(q, k, v, g, b, chunk)
    return _rule(q, k, v, g, b, chunk, "xla")

