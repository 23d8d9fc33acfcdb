"""Micro-bench: fused Pallas blockwise attention vs the XLA einsum merge.

Measures the per-ring-step block compute that dominates sequence-parallel
attention (distributed/sequence_parallel.py): on one chip, attention over
a long sequence computed (a) by the custom Pallas kernel with LSE
residuals (kernels/flash_block.py), (b) by the unfused f32 einsum
online-softmax loop the r2 ring body used, (c) by the library Pallas
flash kernel (no LSE — what the ring CANNOT use). fwd and fwd+bwd.

Run on TPU:  python tools/bench_ring.py
CPU smoke:   JAX_PLATFORMS=cpu python tools/bench_ring.py --smoke
Prints one JSON line with ms per variant and the fused/xla speedup.
"""
import argparse
import functools
import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _timeit(fn, *args, iters=10, warmup=2):
    import jax
    for _ in range(warmup):
        r = fn(*args)
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + interpret mode (CPU)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--blocks", type=int, default=4,
                    help="number of kv blocks (emulates sp ring steps)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.flash_block import (flash_attention_lse,
                                                merge_lse_blocks)

    interpret = args.smoke or jax.default_backend() != "tpu"
    B, S, H, D = 1, (512 if args.smoke else args.seq), args.heads, args.dim
    nb = args.blocks
    sl = S // nb
    scale = 1.0 / D ** 0.5
    dt = jnp.float32 if args.smoke else jnp.bfloat16

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, sl, D), dt)   # one rank's q shard
    ks = jnp.asarray(rng.randn(nb, B, H, sl, D), dt)
    vs = jnp.asarray(rng.randn(nb, B, H, sl, D), dt)

    kern = functools.partial(flash_attention_lse, causal=False,
                             sm_scale=scale, interpret=interpret)

    @jax.jit
    def fused(q, ks, vs):
        # ring-step emulation: merge nb kernel calls via LSE
        acc = jnp.zeros((B, H, sl, D), jnp.float32)
        lse = jnp.full((B, H, sl), -jnp.inf, jnp.float32)
        for i in range(nb):
            o, l = kern(q, ks[i], vs[i])
            acc, lse = merge_lse_blocks(acc, lse, o.astype(jnp.float32), l)
        return acc

    @jax.jit
    def xla_merge(q, ks, vs):
        # the r2 ring body: unfused f32 einsums + online softmax
        q32 = q.astype(jnp.float32)
        acc = jnp.zeros((B, H, sl, D), jnp.float32)
        m = jnp.full((B, H, sl), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, H, sl), jnp.float32)
        for i in range(nb):
            s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                           ks[i].astype(jnp.float32)) * scale
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vs[i].astype(jnp.float32))
            m = m_new
        return acc / l[..., None]

    res = {"seq": S, "heads": H, "dim": D, "blocks": nb,
           "dtype": str(dt.__name__ if hasattr(dt, "__name__") else dt)}
    res["fused_fwd_ms"] = round(_timeit(fused, q, ks, vs), 3)
    res["xla_fwd_ms"] = round(_timeit(xla_merge, q, ks, vs), 3)

    def loss_f(q, ks, vs):
        return (fused(q, ks, vs) ** 2).sum()

    def loss_x(q, ks, vs):
        return (xla_merge(q, ks, vs) ** 2).sum()

    gf = jax.jit(jax.grad(loss_f, argnums=(0, 1, 2)))
    gx = jax.jit(jax.grad(loss_x, argnums=(0, 1, 2)))
    res["fused_fwdbwd_ms"] = round(_timeit(gf, q, ks, vs), 3)
    res["xla_fwdbwd_ms"] = round(_timeit(gx, q, ks, vs), 3)
    res["speedup_fwd"] = round(res["xla_fwd_ms"] / res["fused_fwd_ms"], 3)
    res["speedup_fwdbwd"] = round(
        res["xla_fwdbwd_ms"] / res["fused_fwdbwd_ms"], 3)

    try:  # library kernel (no LSE residuals) for context, fwd only
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as lib_flash)
        if not interpret:
            full_k = ks.swapaxes(0, 1).reshape(B, H, S, D)
            full_v = vs.swapaxes(0, 1).reshape(B, H, S, D)

            @jax.jit
            def lib(q, k, v):
                return lib_flash(q, k, v, causal=False, sm_scale=scale)
            res["lib_full_fwd_ms"] = round(
                _timeit(lib, q, full_k, full_v), 3)
    except Exception as e:  # pragma: no cover - informational only
        res["lib_error"] = repr(e)[:120]

    print(json.dumps(res))


if __name__ == "__main__":
    main()
