"""Pallas blockwise flash kernel (kernels/flash_block.py) + fused ring path,
and the library's splash kernel as nn/functional/flash_attention.py calls it.

Runs in interpret mode on the CPU mesh; the same code compiles on TPU.
Reference semantics: paddle/phi/kernels/gpu/flash_attn_kernel.cu (fused
attention with LSE residuals) — numerics checked against plain softmax
attention, like the reference's test_flash_attention.py does vs
scaled_dot_product_attention.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.sequence_parallel import (_fused_geometry_ok,
                                                      last_ring_dispatch)
from paddle_tpu.kernels.flash_block import (flash_attention_lse,
                                            flash_block_attention,
                                            merge_lse_blocks)


@pytest.fixture(autouse=True)
def fresh_mesh():
    dist.set_mesh(None)
    yield
    dist.set_mesh(None)


def _ref(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        S = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v), lse


def _rand(*shape, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype("float32"))


@pytest.mark.parametrize("causal", [False, True])
def test_kernel_forward_and_lse(causal):
    B, H, S, D = 2, 3, 256, 64
    q, k, v = (_rand(B, H, S, D, seed=i) for i in range(3))
    out, lse = flash_attention_lse(q, k, v, causal=causal, interpret=True)
    ro, rl = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ro), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rl), atol=2e-5)


def test_kernel_grads_including_lse_cotangent(causal=True):
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (_rand(B, H, S, D, seed=i) for i in range(3))
    co = _rand(B, H, S, D, seed=7)
    cl = _rand(B, H, S, seed=8)

    def loss_kern(q, k, v):
        o, l = flash_attention_lse(q, k, v, causal=causal, interpret=True)
        return (o * co).sum() + (l * cl).sum()

    def loss_ref(q, k, v):
        o, l = _ref(q, k, v, causal)
        return (o * co).sum() + (l * cl).sum()

    gk = jax.grad(loss_kern, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_block_offsets_match_sliced_full_attention():
    """Global-position causal masking: merging per-block kernel calls with
    offsets must equal full causal attention (the ring schedule)."""
    B, H, S, D, sl = 1, 2, 512, 64, 128
    q, k, v = (_rand(B, H, S, D, seed=i) for i in range(3))
    ro, _ = _ref(q, k, v, True)
    scale = 1.0 / np.sqrt(D)
    for qi in range(S // sl):
        qs = q[:, :, qi * sl:(qi + 1) * sl]
        acc = jnp.zeros((B, H, sl, D), jnp.float32)
        lse = jnp.full((B, H, sl), -jnp.inf, jnp.float32)
        for ki in range(S // sl):
            o_i, l_i = flash_block_attention(
                qs, k[:, :, ki * sl:(ki + 1) * sl],
                v[:, :, ki * sl:(ki + 1) * sl],
                float(qi * sl), float(ki * sl), True, scale, 128, 128,
                True)
            acc, lse = merge_lse_blocks(acc, lse, o_i, l_i)
        np.testing.assert_allclose(
            np.asarray(acc), np.asarray(ro[:, :, qi * sl:(qi + 1) * sl]),
            atol=2e-5)


def test_attention_dispatch_gate_at_bench_geometry():
    """The GPT-125M bench geometry (seq 1024, head_dim 64, no dropout)
    must pass the Pallas gate; dispatch decisions must be observable."""
    from paddle_tpu.nn.functional.flash_attention import (
        _pallas_geometry_ok, last_attention_dispatch)
    assert _pallas_geometry_ok(1024, 64, 0.0)
    assert _pallas_geometry_ok(2048, 128, 0.0)
    assert not _pallas_geometry_ok(100, 64, 0.0)    # seq doesn't tile
    assert not _pallas_geometry_ok(1024, 192, 0.0)  # bad head_dim
    assert not _pallas_geometry_ok(1024, 64, 0.1)   # dropout
    # on CPU the runtime dispatch records the xla fallback with a reason
    import paddle_tpu.nn.functional as F
    q = paddle.to_tensor(np.zeros((1, 128, 2, 64), "float32"))
    F.flash_attention(q, q, q)[0]
    d = last_attention_dispatch()
    assert d["backend"] == "xla" and "TPU" in d["reason"]


def _plain_attention(q, k, v, causal, scale):
    """Plain f32 softmax attention on [b, s, h, d]; the causal mask is
    aligned to the first row and column, as the kernel's is."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("s_q,s_k", [(256, 256), (128, 384)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128])
def test_splash_call_matches_plain_attention(d, causal, s_q, s_k):
    """_pallas_flash_local itself (interpret mode off the chip): the
    scaling of q, the layout changes, the vmap over the batch and the
    fused backward, against plain attention in all three gradients."""
    from paddle_tpu.nn.functional.flash_attention import _pallas_flash_local
    B, H = 2, 3
    q = _rand(B, s_q, H, d, seed=1)
    k, v = _rand(B, s_k, H, d, seed=2), _rand(B, s_k, H, d, seed=3)
    co = _rand(B, s_q, H, d, seed=4)
    scale = 1.0 / np.sqrt(d)

    def run(attn):
        return jax.value_and_grad(
            lambda q, k, v: (attn(q, k, v, causal, scale) * co).sum(),
            argnums=(0, 1, 2))(q, k, v)

    (out, grads), (ro, rg) = run(_pallas_flash_local), run(_plain_attention)
    np.testing.assert_allclose(float(out), float(ro), rtol=2e-5)
    for g, r in zip(grads, rg):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-5)


def test_splash_kernel_is_built_once_a_geometry():
    """Mask tables are numpy work at trace time: twelve unrolled layers,
    and every later trace, share one kernel object."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    q = jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.float32)

    def two_layers(q, k, v):
        o = fa._pallas_flash_local(q, k, v, True, 0.125)
        return fa._pallas_flash_local(o, k, v, True, 0.125)

    fa._splash_kernel.cache_clear()
    jax.eval_shape(two_layers, q, q, q)
    jax.eval_shape(jax.grad(lambda *a: two_layers(*a).sum()), q, q, q)
    info = fa._splash_kernel.cache_info()
    assert (info.misses, info.currsize) == (1, 1) and info.hits == 3
    jax.eval_shape(lambda q, k, v: fa._pallas_flash_local(
        q, k, v, False, 0.125), q, q, q)            # another mask: another
    assert fa._splash_kernel.cache_info().currsize == 2


def _pallas_calls(jaxpr, found=None):
    """Pallas kernels of a jaxpr by name, through every nested jaxpr
    (checkpoint, scan, custom_vjp, vmap bodies)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            found[name] = found.get(name, 0) + 1
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, found)
    return found


# what reaches _pallas_flash_local: GPT's head-major MHA call, afmoe's
# grouped call with a window, latent attention's 192-wide q/k on 128-wide v
_RESIDUAL_CALLS = {
    "mha_head_major": dict(q=(2, 4, 256, 64), kv=(2, 4, 256, 64),
                           v=(2, 4, 256, 64), head_axis=1, scale=None,
                           window=None, kernel="splash_mha"),
    "grouped_window": dict(q=(2, 256, 4, 64), kv=(2, 256, 2, 64),
                           v=(2, 256, 2, 64), head_axis=2, scale=0.125,
                           window=128, kernel="splash_mqa"),
    "heads_192_128": dict(q=(1, 2, 256, 192), kv=(1, 2, 256, 192),
                          v=(1, 2, 256, 128), head_axis=1, scale=None,
                          window=None, kernel="splash_mha"),
}


def _residual_block(call):
    """A block as recomputation sees one: a product into the kernel and a
    product out of it, and the operands to take its gradient at."""
    from paddle_tpu.nn.functional.flash_attention import _pallas_flash_local
    q, k, v = (_rand(*call[n], seed=i) * 0.3
               for i, n in enumerate(("q", "kv", "v")))
    w = _rand(call["q"][-1], call["q"][-1], seed=5) * 0.1

    def block(q, k, v, w):
        out = _pallas_flash_local(q @ w, k, v, True, call["scale"],
                                  window=call["window"],
                                  head_axis=call["head_axis"])
        return jnp.tanh(out) * 2.0

    return block, (q, k, v, w)


def _policies():
    from paddle_tpu.distributed.recompute import resolve_checkpoint_policy
    return {"full": resolve_checkpoint_policy("full"),
            "dots": resolve_checkpoint_policy("dots"),
            "nothing": jax.checkpoint_policies.nothing_saveable}


@pytest.mark.parametrize("policy,forwards",
                         [("full", 1), ("dots", 1), ("nothing", 2)])
@pytest.mark.parametrize("call", list(_RESIDUAL_CALLS))
def test_recomputed_block_keeps_the_kernels_result(call, policy, forwards):
    """Under the named policies the gradient of a checkpointed block runs
    the forward kernel once (its result and logsumexp are kept for the
    fused backward kernel), under nothing_saveable twice; the backward
    kernel once either way."""
    call = _RESIDUAL_CALLS[call]
    block, args = _residual_block(call)
    remat = jax.checkpoint(block, policy=_policies()[policy])
    grad = jax.grad(lambda *a: remat(*a).sum(), argnums=(0, 1, 2, 3))
    found = _pallas_calls(jax.make_jaxpr(grad)(*args).jaxpr)
    assert found == {call["kernel"] + "_fwd_residuals": forwards,
                     call["kernel"] + "_dkv_no_residuals": 1}


@pytest.mark.parametrize("call", list(_RESIDUAL_CALLS))
def test_kept_result_gives_the_unnamed_kernels_gradients(call, monkeypatch):
    """The kept result and logsumexp are the ones a second run would have
    produced: gradients bitwise those of a kernel built without the name,
    whose block is recomputed whole."""
    import importlib
    fa = importlib.import_module("paddle_tpu.nn.functional.flash_attention")
    block, args = _residual_block(_RESIDUAL_CALLS[call])

    def grads(policy):
        remat = jax.checkpoint(block, policy=policy)
        return jax.jit(jax.grad(lambda *a: remat(*a).sum(),
                                argnums=(0, 1, 2, 3)))(*args)

    kept = grads(_policies()["full"])
    monkeypatch.setattr(fa, "ATTENTION_RESIDUAL", None)
    fa._splash_kernel.cache_clear()
    try:
        plain = grads(None)
    finally:
        fa._splash_kernel.cache_clear()     # no un-named kernel stays
    for a, b in zip(kept, plain):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("policy,forwards",
                         [("full", 1), ("dots", 1), ("nothing", 2)])
def test_scanned_recomputed_body_keeps_the_kernels_result(policy, forwards):
    """The same count where ScannedStack puts the checkpoint: on the body
    of a lax.scan over stacked weights (the forward scan holds the kernel
    once; the backward scan holds it again only if nothing was kept)."""
    call = _RESIDUAL_CALLS["mha_head_major"]
    block, (q, k, v, w) = _residual_block(call)
    body = jax.checkpoint(lambda h, w: block(h, k, v, w),
                          policy=_policies()[policy])

    def stack(h, ws):
        return jax.lax.scan(lambda h, w: (body(h, w), None), h, ws)[0].sum()

    found = _pallas_calls(jax.make_jaxpr(jax.grad(stack, argnums=(0, 1)))(
        q, jnp.stack([w, w, w])).jaxpr)
    assert found == {"splash_mha_fwd_residuals": forwards,
                     "splash_mha_dkv_no_residuals": 1}


def test_dispatch_record_names_kernel_and_blocks(monkeypatch):
    """What the driver prints and chip_smoke.py records: backend, the
    library kernel that engaged and the blocks the rule gave it."""
    import importlib

    import paddle_tpu.nn.functional as F
    from paddle_tpu.core.tensor import Tensor
    fa_mod = importlib.import_module(
        "paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setattr(fa_mod, "_on_tpu", lambda: True)
    for (s, d), blocks in {
            (1024, 64): {"q": 1024, "kv": 1024, "kv_compute": 512},
            (2048, 128): {"q": 1024, "kv": 1024, "kv_compute": 512},
            (384, 64): {"q": 384, "kv": 384, "kv_compute": 384}}.items():
        q = jax.ShapeDtypeStruct((2, s, 4, d), jnp.bfloat16)
        # traced only: off the chip nothing can run the compiled kernel
        jax.eval_shape(lambda q, k, v: F.flash_attention(
            Tensor(q), Tensor(k), Tensor(v), causal=True)[0].value, q, q, q)
        rec = fa_mod.last_attention_dispatch()
        assert rec["backend"] == "pallas" and rec["reason"] == "ok"
        assert rec["kernel"] == "splash_fused" and rec["blocks"] == blocks
        # the kernel in the window names its result for recomputation
        assert rec["residual"] == fa_mod.ATTENTION_RESIDUAL


def test_require_pallas_flag_raises(monkeypatch):
    import importlib

    import paddle_tpu.nn.functional as F
    fa_mod = importlib.import_module(
        "paddle_tpu.nn.functional.flash_attention")
    monkeypatch.setenv("PADDLE_TPU_REQUIRE_PALLAS", "1")
    monkeypatch.setattr(fa_mod, "_on_tpu", lambda: True)
    q = paddle.to_tensor(np.zeros((1, 100, 2, 64), "float32"))
    with pytest.raises(RuntimeError, match="REQUIRE_PALLAS"):
        F.flash_attention(q, q, q)


def test_geometry_gate():
    assert _fused_geometry_ok(128, 64)
    assert _fused_geometry_ok(512, 128)
    assert _fused_geometry_ok(256, 256)
    assert not _fused_geometry_ok(100, 64)   # sl doesn't tile
    assert not _fused_geometry_ok(128, 192)  # head_dim >128, not %128


@pytest.mark.parametrize("causal", [False, True])
def test_fused_ring_matches_plain(causal):
    """sp=4 ring at a 128-tiling geometry must take the Pallas path and
    match single-device attention (this is the dispatch regression test:
    it FAILS if the fused kernel stops being selected)."""
    dist.init_mesh({"sp": 4})
    B, S, H, D = 1, 512, 2, 64
    rng = np.random.RandomState(3)
    q, k, v = (rng.randn(B, S, H, D).astype("float32") for _ in range(3))
    out = dist.ring_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                              paddle.to_tensor(v), causal=causal)
    disp = last_ring_dispatch()
    assert disp["path"] == "pallas", disp
    # reference in (B,S,H,D) layout
    qh, kh, vh = (jnp.swapaxes(jnp.asarray(a), 1, 2) for a in (q, k, v))
    ro, _ = _ref(qh, kh, vh, causal)
    np.testing.assert_allclose(out.numpy(),
                               np.asarray(jnp.swapaxes(ro, 1, 2)),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_ulysses_matches_plain(causal):
    """Ulysses with the full-sequence geometry tiling 128 runs its local
    attention in the fused kernel; outputs must match plain attention."""
    dist.init_mesh({"sp": 4})
    B, S, H, D = 1, 512, 4, 64
    rng = np.random.RandomState(5)
    q, k, v = (rng.randn(B, S, H, D).astype("float32") for _ in range(3))
    out = dist.ulysses_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                                 paddle.to_tensor(v), causal=causal)
    qh, kh, vh = (jnp.swapaxes(jnp.asarray(a), 1, 2) for a in (q, k, v))
    ro, _ = _ref(qh, kh, vh, causal)
    np.testing.assert_allclose(out.numpy(),
                               np.asarray(jnp.swapaxes(ro, 1, 2)),
                               rtol=2e-4, atol=2e-5)


def test_fused_ring_backward_matches_plain():
    dist.init_mesh({"sp": 4})
    B, S, H, D = 1, 512, 2, 64
    rng = np.random.RandomState(4)
    qn, kn, vn = (rng.randn(B, S, H, D).astype("float32")
                  for _ in range(3))
    q = paddle.to_tensor(qn, stop_gradient=False)
    k = paddle.to_tensor(kn, stop_gradient=False)
    v = paddle.to_tensor(vn, stop_gradient=False)
    out = dist.ring_attention(q, k, v, causal=True)
    assert last_ring_dispatch()["path"] == "pallas"
    paddle.mean(out).backward()

    # reference grads via jax on the unsharded computation
    def loss(qv, kv, vv):
        o, _ = _ref(jnp.swapaxes(qv, 1, 2), jnp.swapaxes(kv, 1, 2),
                    jnp.swapaxes(vv, 1, 2), True)
        return jnp.mean(jnp.swapaxes(o, 1, 2))

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn))
    np.testing.assert_allclose(q.grad.numpy(), np.asarray(gq), atol=1e-5)
    np.testing.assert_allclose(k.grad.numpy(), np.asarray(gk), atol=1e-5)
    np.testing.assert_allclose(v.grad.numpy(), np.asarray(gv), atol=1e-5)
